"""The busiest server loop's time by layer, from the program's counters
``loop.layer_ns`` (keyed ``<loop>/<layer>``: ratis_tpu/trace/tracer.py,
LoopClock) and ``loop.select_ns`` / ``loop.iterations`` (keyed by loop).
The busiest loop is the one ``loop_busy_pct.py`` reads: the least selector
time among the loops that iterated in the session.  A program without the
layer clock has no ``loop.layer_ns``: every reading is then None."""


def ms_per_commit(ctx, layer: str):
    """Milliseconds of the busiest loop charged to ``layer`` over the
    session, per acknowledged operation of the window."""
    from ratis_tpu.trace import TRACER
    if not hasattr(TRACER, "session") or not ctx["acked_in_window"]:
        return None
    sess = TRACER.session()
    if not sess["t_on"] or not sess["t_off"]:
        return None
    keyed = sess["keyed"]
    iterations = keyed.get("loop.iterations", {})
    waits = {key: ns for key, ns in keyed.get("loop.select_ns", {}).items()
             if iterations.get(key, 0) > 0}
    if not waits:
        return None
    busiest = min(waits, key=waits.get)
    ns = keyed.get("loop.layer_ns", {}).get(f"{busiest}/{layer}")
    if ns is None:
        return None
    return ns / 1e6 / ctx["acked_in_window"]
