"""Share of the traced window in which the device runs no program while
the host is in the block upkeep and token emission of a scheduler round
(the ``serve.grow_blocks`` and ``serve.emit`` phases of
``serve.round``)."""

PHASES = ("serve.grow_blocks", "serve.emit")


def read(run):
    from bench import phases
    got = phases.find(run)
    if not got or not phases.rounds(got) or not got["window_s"]:
        return None
    idle = got["idle_by_phase"]
    return 100.0 * sum(idle.get(p, 0.0) for p in PHASES) / got["window_s"]
