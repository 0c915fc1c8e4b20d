"""Sharded lowering smoke (subprocess: needs its own XLA device count)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import ARCHS
    from repro.configs.base import MeshConfig, RunConfig, ShapeConfig
    from repro.models import build_model, input_pspecs, input_specs
    from repro.models.common import Topo, make_mesh_from_config
    from repro.train.step import make_train_step, state_pspecs, state_shapes

    mcfg = MeshConfig(shape=(4, 4), axis_names=("data", "model"))
    mesh = make_mesh_from_config(mcfg)
    topo = Topo(mcfg)
    out = {}
    for arch in ["glm4-9b", "falcon-mamba-7b", "deepseek-v2-236b",
                 "phi3-medium-14b"]:
        cfg = ARCHS[arch].reduced(num_layers=2, d_model=256, num_heads=8,
                                  head_dim=32, d_ff=512, vocab_size=1024)
        shape = ShapeConfig("small", seq_len=128, global_batch=8, kind="train")
        model = build_model(cfg, topo, kind="train")
        step = make_train_step(model, RunConfig(microbatches=2), topo)
        ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                    is_leaf=lambda x: isinstance(x, P))
        with mesh:
            compiled = jax.jit(
                step,
                in_shardings=(ns(state_pspecs(model, topo)),
                              ns(input_pspecs(cfg, shape, topo))),
                out_shardings=(ns(state_pspecs(model, topo)), None),
                donate_argnums=(0,),
            ).lower(state_shapes(model, RunConfig()),
                    input_specs(cfg, shape)).compile()
        txt = compiled.as_text()
        out[arch] = {
            "collectives": sum(txt.count(k) > 0 for k in
                               ("all-reduce", "all-gather", "reduce-scatter",
                                "all-to-all")),
            "flops": compiled.cost_analysis().get("flops", 0.0),
        }
    print("RESULT" + json.dumps(out))
""")


@pytest.mark.slow
def test_reduced_models_lower_on_4x4_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")][0]
    out = json.loads(line[len("RESULT"):])
    assert set(out) == {"glm4-9b", "falcon-mamba-7b", "deepseek-v2-236b",
                        "phi3-medium-14b"}
    for arch, rec in out.items():
        assert rec["collectives"] >= 1, arch   # SPMD actually partitioned
        assert rec["flops"] > 0
