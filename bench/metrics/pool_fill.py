"""Device program: the share, in %, of the fused launches' pool slots that
hold a chain block the launches' queries own (``EngineStats.pool_blocks``
over ``.pool_slots``, summed over both images, over the window).  Nothing
to read where the program keeps no such counters or no launch ran; moves
``query_p50_ms``."""


def read(run):
    if "pool_slots" not in run.after:
        return None
    slots = run.delta("pool_slots")
    return 100.0 * run.delta("pool_blocks") / slots if slots else None
