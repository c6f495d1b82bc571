"""Device-to-host reads per scheduler round: the program's ``serve.sync``
spans over its ``serve.round`` spans in the traced window."""


def read(run):
    from bench import phases
    got = phases.find(run)
    n = got and phases.rounds(got)
    if not n:
        return None
    return sum(sp["name"] == "serve.sync" for sp in got["spans"]) / n
