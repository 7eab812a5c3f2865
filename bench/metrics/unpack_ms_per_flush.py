"""Engine and host prep (``engine/device_backend.fused_execute``): host
time per flush spent turning the launches' read-back arrays into
per-query results, in ms: the window's ``repro.device.unpack`` span time
(``EngineStats.spans``) over its flushes.  Nothing to read without the
span table; moves ``query_p50_ms``."""

from bench.lib import spans


def read(run):
    n = spans.flushes(run)
    if not n:
        return None
    return 1e3 * spans.delta(run, "repro.device.unpack")[1] / n
