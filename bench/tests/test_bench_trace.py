"""The reduction from a profiler trace to busy time, idle gaps and kernel
time, on a small recorded trace."""
import pytest
from jax.profiler import ProfileData

import bench_rehearsal  # noqa: F401
from bench import tracing

# times in ps from each line's base (ns): a 10 ms window on the host, two
# device ops that overlap, then the kernel, with the host in named spans
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 2000000000 }
    events { metadata_id: 1 offset_ps: 2000000000 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 7000000000 duration_ps: 1000000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.7" } }
  event_metadata { key: 2 value { id: 2 name: "spike_hist_packed" } }
  event_metadata { key: 3 value { id: 3 name: "jit_spike_hist_packed" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 4000000000 duration_ps: 3000000000 }
    events { metadata_id: 3 offset_ps: 8000000000 duration_ps: 2000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.classify" } }
  event_metadata { key: 3 value { id: 3 name: "bench.retire" } }
}
"""


def test_reduction_of_a_small_trace():
    red = tracing.reduce(ProfileData.from_text_proto(TRACE))
    assert red["window_s"] == pytest.approx(10e-3)
    assert red["busy_s"] == pytest.approx(4e-3)      # [1,4] + [7,8] ms
    assert red["devices"] == 1
    assert red["op_s"]["spike_hist_packed"] == pytest.approx(1e-3)
    assert red["op_s"]["fusion.7"] == pytest.approx(4e-3)
    assert "jit_spike_hist_packed" not in red["op_s"]
    assert red["ops"][0] == ["fusion.7", pytest.approx(4e-3)]
    # gaps: [4,7] in classify, [8,10] in retire, [0,1] before any span
    assert red["gaps"] == [["classify", pytest.approx(3e-3)],
                           ["retire", pytest.approx(2e-3)],
                           ["none", pytest.approx(1e-3)]]


def test_a_trace_without_the_window_is_refused():
    bad = TRACE.replace('"bench.window"', '"other"')
    with pytest.raises(RuntimeError, match="bench.window"):
        tracing.reduce(ProfileData.from_text_proto(bad))
