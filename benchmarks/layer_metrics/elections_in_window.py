"""Consensus: elections any division started during the window (none is
asked for; leaders are appointed before it)."""


def read(ctx):
    return ctx["c1"]["elections"] - ctx["c0"]["elections"]
