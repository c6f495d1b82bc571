"""Device programs run per scheduler round: runs of any program on the
first device in the traced window (pro rata at its edges) over the
program's ``serve.round`` spans in it."""


def read(run):
    from bench import phases
    got = phases.find(run)
    n = got and phases.rounds(got)
    if not n:
        return None
    return sum(run["trace"]["modules"].values()) / n
