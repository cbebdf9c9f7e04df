"""State machine data: payload the leaders put into their entries' sm_data
during the trace session, in MB (10^6 B) per second of it: the counter
``sm.data_bytes`` of the key ``leader`` (models/filestore.py:
start_transaction) over the session's length."""

def read(ctx):
    from ratis_tpu.trace import TRACER
    sess = TRACER.session()
    if not sess["t_on"] or not sess["t_off"]:
        return None
    nbytes = sess["keyed"].get("sm.data_bytes", {}).get("leader")
    if not nbytes:
        return None
    return nbytes / 1e6 / ((sess["t_off"] - sess["t_on"]) / 1e9)
