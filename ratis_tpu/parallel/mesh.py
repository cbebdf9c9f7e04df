"""Mesh sharding of the quorum engine: the multi-chip scaling axis.

The framework's parallelism axis is the *multi-raft group batch* — the
analog of the reference's one-process-many-RaftGroups multiplexing
(RaftServerProxy.ImplMap, RaftServerProxy.java:89): thousands of
independent groups, so the `[G, ...]` state arrays shard cleanly over a
device mesh with NO cross-device collectives in the hot kernel (each
group's quorum math is row-local; XLA's SPMD partitioner keeps the whole
``engine_step`` collective-free, so scaling is embarrassingly linear over
ICI).  Host-side ack events travel one of two ways: the legacy path
replicates them to all devices (the scatter by group id resolves locally
on the device that owns the row), while the production fast tick routes
each event to the owning slice's [7, S, E] plane
(:func:`sliced_event_sharding`) so a device only ever scans the E/S
columns that target rows it holds.

These helpers build the mesh, the in/out shardings for
:func:`ratis_tpu.ops.quorum.engine_step`, and a jitted sharded step —
used by the driver's ``dryrun_multichip``, the benchmark, and any
multi-chip deployment.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

GROUP_AXIS = "groups"


def make_group_mesh(n_devices: Optional[int] = None, devices=None):
    """A 1-D mesh over the group axis (jax.sharding.Mesh)."""
    import jax
    from jax.sharding import Mesh
    if devices is None:
        devices = jax.devices()[:n_devices] if n_devices else jax.devices()
    if n_devices is not None and len(devices) < n_devices:
        raise ValueError(
            f"need {n_devices} devices, have {len(devices)}")
    return Mesh(np.array(devices), axis_names=(GROUP_AXIS,))


def engine_shardings(mesh):
    """(in_shardings tuple, out_shardings EngineStep) for engine_step:
    group-major arrays shard over the mesh, packed ack events and scalars
    replicate."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ratis_tpu.ops.quorum import EngineStep
    grp = NamedSharding(mesh, P(GROUP_AXIS))            # [G]
    grp_peer = NamedSharding(mesh, P(GROUP_AXIS, None))  # [G, P]
    repl = NamedSharding(mesh, P())                      # events / scalars
    in_shardings = (
        grp_peer,  # match_index
        grp_peer,  # last_ack_ms
        repl,      # ev_group
        repl,      # ev_peer
        repl,      # ev_match
        repl,      # ev_time_ms
        repl,      # ev_valid
        grp_peer,  # self_mask
        grp,       # flush_index
        grp_peer,  # conf_cur
        grp_peer,  # conf_old
        grp,       # commit_index
        grp,       # first_leader_index
        grp,       # role
        grp,       # election_deadline_ms
        repl,      # now_ms
        repl,      # leadership_timeout_ms
    )
    out_shardings = EngineStep(grp_peer, grp_peer, grp, grp, grp, grp)
    return in_shardings, out_shardings


def sharded_engine_step(mesh):
    """jit(engine_step) with the group axis sharded over ``mesh``."""
    from ratis_tpu.ops.quorum import engine_step
    from ratis_tpu.util.jaxenv import jit
    in_shardings, out_shardings = engine_shardings(mesh)
    return jit(engine_step, in_shardings=in_shardings,
               out_shardings=out_shardings)


def device_state_shardings(mesh):
    """Sharding for ops.quorum.DeviceState: every [G,...] array shards its
    group axis over the mesh (row-local quorum math means the partitioner
    keeps the resident step collective-free)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ratis_tpu.ops.quorum import DeviceState
    grp = NamedSharding(mesh, P(GROUP_AXIS))
    grp_peer = NamedSharding(mesh, P(GROUP_AXIS, None))
    return DeviceState(
        match_index=grp_peer, last_ack_ms=grp_peer, self_mask=grp_peer,
        conf_cur=grp_peer, conf_old=grp_peer, role=grp,
        flush_index=grp, commit_index=grp, first_leader_index=grp,
        election_deadline_ms=grp)


def sliced_event_sharding(mesh):
    """Sharding for the [7, S, E] pre-routed event planes of
    :func:`ratis_tpu.ops.quorum.engine_step_resident_fast_sliced`: the
    slice axis maps onto the group axis of the mesh, so each device
    receives ONLY its own slice's packed events."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(None, GROUP_AXIS, None))


def sharded_resident_fast_step_sliced(mesh):
    """jit(engine_step_resident_fast_sliced) over ``mesh``: DeviceState
    sharded over the group axis + donated, events slice-routed ([7, S, E],
    slice axis sharded) instead of replicated, the [4, G] packed output
    sharded on its group axis — the production mesh tick.  Each device
    scatters only the E/S event columns that target rows it owns; the
    partitioner keeps the whole step collective-free."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ratis_tpu.ops.quorum import (ResidentFastStep,
                                      engine_step_resident_fast_sliced)
    from ratis_tpu.util.jaxenv import jit
    repl = NamedSharding(mesh, P())
    out_grp = NamedSharding(mesh, P(None, GROUP_AXIS))
    return jit(
        engine_step_resident_fast_sliced,
        in_shardings=(device_state_shardings(mesh),
                      sliced_event_sharding(mesh), repl),
        out_shardings=ResidentFastStep(device_state_shardings(mesh),
                                       out_grp),
        donate_argnums=(0,))


def pad_to_mesh(groups: int, n_devices: int) -> int:
    """Round a group capacity up to the next multiple of the mesh size.
    Padded rows stay ROLE_UNUSED (masked invalid) and cost nothing; this
    replaces the old hard requirement that ``mesh-devices`` divide
    ``max-groups``."""
    n = max(1, int(n_devices))
    return -(-int(groups) // n) * n


def sharded_resident_step(mesh):
    """jit(engine_step_resident): the dirty-row refresh variant of the
    resident tick, DeviceState sharded + donated; refresh rows and packed
    events replicate (the scatter by row index resolves locally)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ratis_tpu.ops.quorum import ResidentStep, engine_step_resident
    from ratis_tpu.util.jaxenv import jit
    repl = NamedSharding(mesh, P())
    grp = NamedSharding(mesh, P(GROUP_AXIS))
    state_sh = device_state_shardings(mesh)
    # state + 18 replicated inputs (11 refresh-row arrays, 5 packed
    # event arrays, now_ms, leadership_timeout_ms)
    in_shardings = (state_sh,) + (repl,) * 18
    out_shardings = ResidentStep(state_sh, grp, grp, grp, grp)
    return jit(engine_step_resident, in_shardings=in_shardings,
               out_shardings=out_shardings, donate_argnums=(0,))


def sharded_ledger_pass(mesh, num_peers: int):
    """jit(ops.ledger.ledger_pass) with the group axis sharded over
    ``mesh``: the telemetry tick reads the same mesh-slice layout the
    resident engine keeps, so a mesh deployment's observability pass
    uploads each host-mirror slice to the device that owns it.  The
    packed output replicates — its per-peer sections are cross-group
    reductions, and collectives are fine OFF the hot path (integer sums
    and exact-f32 counts, so the result is bit-identical to the
    single-device pass; enforced in tests/test_lag_ledger.py)."""
    import functools

    from jax.sharding import NamedSharding, PartitionSpec as P

    from ratis_tpu.ops.ledger import ledger_pass
    from ratis_tpu.util.jaxenv import jit
    grp = NamedSharding(mesh, P(GROUP_AXIS))
    grp_peer = NamedSharding(mesh, P(GROUP_AXIS, None))
    repl = NamedSharding(mesh, P())
    in_shardings = (
        grp,       # role
        grp_peer,  # match_index
        grp,       # commit_index
        grp,       # applied_index
        grp_peer,  # conf_cur
        grp_peer,  # conf_old
        grp_peer,  # self_mask
        grp_peer,  # last_ack_ms
        grp_peer,  # peer_index
        grp,       # prev_commit
        grp,       # prev_valid
        repl,      # now_ms
        repl,      # lag_threshold
        repl,      # up_window_ms
    )
    return jit(functools.partial(ledger_pass, num_peers=num_peers),
               in_shardings=in_shardings, out_shardings=repl)


def shard_device_state(mesh, state):
    """device_put a DeviceState with its group-axis shardings."""
    import jax
    sh = device_state_shardings(mesh)
    return type(state)(*(jax.device_put(a, s)
                         for a, s in zip(state, sh)))


def shard_batch(mesh, args: Sequence):
    """device_put every engine_step arg with its proper sharding; the group
    axis size must be divisible by the mesh size."""
    import jax
    import jax.numpy as jnp
    in_shardings, _ = engine_shardings(mesh)
    g = np.shape(args[0])[0]
    n = mesh.devices.size
    if g % n != 0:
        raise ValueError(f"group count {g} not divisible by mesh size {n}")
    return [jax.device_put(jnp.asarray(a), s)
            for a, s in zip(args, in_shardings)]
