"""Host time of the engine per work tick, in ms: every tick phase but the
fences (``serve/telemetry.py`` ``TICK_PHASES`` less ``*.wait``), from the
engine's exact ``stats()`` counters ``tick_phase_s`` and ``work_ticks``,
differenced over the window. Absent for a program without them."""


def read(run):
    s0, s1 = run.stats0, run.stats1
    if "tick_phase_s" not in s1 or "tick_phase_s" not in s0:
        return None
    ticks = s1["work_ticks"] - s0["work_ticks"]
    host = sum(v - s0["tick_phase_s"].get(p, 0.0)
               for p, v in s1["tick_phase_s"].items()
               if not p.endswith(".wait"))
    return 1e3 * host / ticks if ticks > 0 else None
