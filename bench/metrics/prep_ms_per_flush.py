"""Engine and host prep (``serve/query_service.py``, ``engine/engine.py``,
``engine/device_backend.py``): host time per flush up to the launch, in
ms.  The window's self time of the program's spans
``repro.service.flush``, ``repro.engine.execute``, ``repro.engine.plan``,
``repro.device.refresh``, ``repro.device.prep`` and
``repro.device.launch`` (``EngineStats.spans`` before and after the
window) over its flushes.  Nothing to read without the span table; moves
``query_p50_ms``."""

from bench.lib import spans

STAGES = ("repro.service.flush", "repro.engine.execute", "repro.engine.plan",
          "repro.device.refresh", "repro.device.prep", "repro.device.launch")


def read(run):
    n = spans.flushes(run)
    if not n:
        return None
    own = sum(spans.delta(run, name)[2] for name in STAGES)
    return 1e3 * own / n
