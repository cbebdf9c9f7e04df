"""The table of device peaks and the functions that compute the bytes a
quorum-engine dispatch has to move, from shapes alone.  The yardstick: it
reads the same work whatever implements the step."""

from __future__ import annotations

# keyed by jax's device_kind; a device that is not here is an error
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 16 GB HBM2e, "
                  "819 GB/s",
    },
}

# XLA module names of the production steps (jit_<function name>)
FAST_STEP = "engine_step_resident_fast"
REFRESH_STEP = "engine_step_resident"
MIN_BUCKET = 64  # QuorumEngine._bucket's smallest pad of events / dirty rows


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


def device_state_bytes(groups: int, peers: int) -> int:
    """One copy of every DeviceState array at capacity [groups, peers]:
    match_index and last_ack_ms int32 [G,P]; self_mask, conf_cur, conf_old
    bool [G,P]; role int8 [G]; flush_index, commit_index,
    first_leader_index, election_deadline_ms int32 [G]."""
    return 2 * groups * peers * 4 + 3 * groups * peers + groups * 17


def _written_bytes(groups: int, peers: int) -> int:
    """The arrays a step returns changed: match_index, last_ack_ms [G,P]
    int32; flush_index, commit_index, election_deadline_ms [G] int32."""
    return 2 * groups * peers * 4 + 3 * groups * 4


def fast_step_bytes(groups: int, peers: int, events: int = MIN_BUCKET) -> int:
    """Least traffic of one fast dispatch: read the state once, write what
    changes, the [7, E] int32 event pack in, the [4, G] int32 result out."""
    return (device_state_bytes(groups, peers) + _written_bytes(groups, peers)
            + 7 * events * 4 + 4 * groups * 4)


def refresh_step_bytes(groups: int, peers: int, dirty: int = MIN_BUCKET,
                       events: int = MIN_BUCKET) -> int:
    """Least traffic of one dirty-row refresh dispatch: the fast step's
    state read and write, ``dirty`` whole rows in (index + every field),
    ``events`` unpacked acks in (4 int32 + 1 bool), and the [G] outputs
    (new_commit int32, three bool masks)."""
    row = 4 + 2 * peers * 4 + 3 * peers + 17
    return (device_state_bytes(groups, peers) + _written_bytes(groups, peers)
            + dirty * row + events * 17 + groups * 7)
