"""The wire (``bench/traffic``): the 95th percentile of how late each
chunk was handed to ``ingest_tick`` after its due time.  Moves
``decision_p95_ms``."""
import statistics


def read(layer):
    late = layer["wire_late_ms"]
    if len(late) < 20:
        return None
    return statistics.quantiles(late, n=20)[-1]
