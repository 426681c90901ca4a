"""SPK401-402 fixture corpus — metrics-schema agreement. Parsed, never
imported. Line numbers asserted in tests/test_lint.py.

Emit sites for fixture-only events are SPK402-suppressed (they are
intentionally absent from the committed repo schema); the consumers
below are then checked against the live registry these emits create.
"""


def emit(metrics, step, loss):
    metrics.log("fixture_tick", step=step, loss=loss)   # spk: disable=SPK402
    metrics.log("fixture_round", kind="fixture_sync")   # spk: disable=SPK402


def emit_unregistered(metrics):
    metrics.log("fixture_orphan", a=1)                  # SPK402 unregistered


def emit_drifted(metrics):
    metrics.log("ghost_reaped", bogus_field=1)          # SPK402 field drift


def consume(e):
    if e.get("event") == "fixture_tick":                # emitted: no finding
        return 1
    if e.get("event") == "fixture_tikc":                # SPK401 typo
        return 2
    kind = e.get("event", "?")
    if kind == "fixture_round":                         # via local: no finding
        return 3
    if kind in ("fixture_rnd", "summary"):              # SPK401 (fixture_rnd)
        return 4
    return 0


def tolerated(e):
    if e.get("event") == "fixture_ghost":               # spk: disable=SPK401
        return 1
    return 0
