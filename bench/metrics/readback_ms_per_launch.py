"""Engine and host prep (``engine/device_backend.fused_execute``): mean
length, in ms, of the window's ``repro.device.readback`` spans
(``EngineStats.spans``): the pull of one launch's results to the host,
timed after the ``repro.device.wait`` span has waited for the program, so
the transfer and its synchronisation alone (ROADMAP S4).  Nothing to read
without the span table; moves ``query_p50_ms``."""

from bench.lib import spans


def read(run):
    d = spans.delta(run, "repro.device.readback")
    if d is None or not d[0]:
        return None
    return 1e3 * d[1] / d[0]
