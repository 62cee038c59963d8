"""VMEM-fit model for the whole-episode megakernel.

One megakernel grid instance owns one session's full episode, so everything
that must stay resident per instance is easy to enumerate: the packed
learner state (4 nets' weights/biases + both Adam moment sets), the FIFO
replay window, the gathered+packed minibatch workspace, the per-step trace,
the pre-drawn exploration inputs, and the env-model state. A Pallas OOM on
an oversized (chunk, capacity, space) combo names an internal buffer and
nothing else; this model rejects the combo BEFORE the kernel is built, with
the top contributors and the knobs that shrink them.

The chunk size itself does not change the per-instance VMEM footprint (the
grid serializes instances; extra sessions cost HBM, which ``core.fleet.
memory_plan`` accounts) — it is named in the error so the message describes
the launch the caller actually asked for.
"""

from __future__ import annotations

import math

# VMEM a Pallas kernel's pipelined blocks may use under the compiler's
# default limits, keyed by ``device_kind`` as JAX reports it. TPU v5e
# ("TPU v5 lite"): compiling for a described v5e chip, four double-buffered
# 4 MiB blocks (16 MiB) are accepted and 4 MiB + 16 KiB each are refused;
# the chip has 128 MiB of VMEM in all. Pallas double-buffers HBM<->VMEM
# block copies, which the pipeline factor covers.
VMEM_BYTES = {"TPU v5 lite": 16 * 2 ** 20}
# what an interpret-mode kernel (no chip) is checked against: the v5e this
# repository runs on
TARGET_DEVICE_KIND = "TPU v5 lite"
_PIPELINE_FACTOR = 2


def vmem_budget(device_kind: str) -> int:
    """The VMEM budget of ``device_kind``; an unknown device raises."""
    try:
        return VMEM_BYTES[device_kind]
    except KeyError:
        raise ValueError(
            f"no VMEM budget is known for device kind {device_kind!r} "
            f"(known: {sorted(VMEM_BYTES)}); measure it with the device's "
            f"compiler and add it to roofline.vmem.VMEM_BYTES") from None

# fields of the packed learner layout (kernels.ddpg_fused.pack_params):
# weights [4,L,P,P] + biases [4,L,P] + mom_w [2,2,L,P,P] + mom_b [2,2,L,P]
# + counts [2], all f32/i32 (4 bytes)
_NUM_LAYERS = 3


def episode_vmem_plan(*, steps, capacity, state_dim, action_dim, hidden,
                      num_updates, batch_size, pad, device_kind,
                      env_state_bytes=0):
    """Byte budget of ONE megakernel grid instance (one session's episode)
    against the VMEM of ``device_kind``.

    Returns ``{"contributions": {name: bytes}, "per_session_bytes",
    "pipelined_bytes", "budget_bytes", "fits"}``. ``pad`` is the packed
    lane width P from ``kernels.ddpg_fused.packed_dims``;
    ``env_state_bytes`` the flattened env-state leaf bytes per session.
    """
    budget = vmem_budget(device_kind)
    P = int(pad)
    L = _NUM_LAYERS
    k, m = int(state_dim), int(action_dim)
    if len(tuple(hidden)) + 1 != L:
        raise ValueError(f"hidden={hidden!r}: packed layout is {L}-layer")
    contributions = {
        # 4 live nets + 2x2 Adam moments over the same shapes
        "learner_packed": (4 + 4) * L * P * P * 4
                          + (4 + 4) * L * P * 4 + 2 * 4,
        # FIFO window: s [cap,k], a [cap,m], r [cap], s2 [cap,k]
        "replay_window": int(capacity) * (2 * k + m + 1) * 4,
        # gathered minibatches packed to P lanes: sx/cx/s2x/rx [U,B,P]
        "minibatch_workspace": int(num_updates) * int(batch_size)
                               * 4 * P * 4,
        # trace: action_idx [T,m] i32 + metrics [T,k] + rewards/objectives
        # f32 + restarts i32
        "trace": int(steps) * (m * 4 + k * 4 + 12),
        # pre-drawn exploration inputs: warmup/noise [T,m] + use_warmup [T]
        "exploration_inputs": int(steps) * (2 * m * 4 + 1),
        "env_state": int(env_state_bytes),
    }
    per_session = sum(contributions.values())
    pipelined = _PIPELINE_FACTOR * per_session
    return {
        "contributions": contributions,
        "per_session_bytes": per_session,
        "pipelined_bytes": pipelined,
        "budget_bytes": budget,
        "fits": pipelined <= budget,
    }


_REMEDIES = {
    "replay_window": "shrink buffer capacity",
    "minibatch_workspace": "lower updates_per_step or batch_size",
    "trace": "run fewer steps per scan (smaller T)",
    "exploration_inputs": "run fewer steps per scan (smaller T)",
    "learner_packed": "smaller hidden widths (pad width P tracks them)",
    "env_state": "slim the env-model state",
}


def check_episode_vmem_fit(*, chunk, steps, capacity, state_dim, action_dim,
                           hidden, num_updates, batch_size, pad, device_kind,
                           env_state_bytes=0):
    """Raise ``ValueError`` with an actionable message when one episode's
    working set cannot stay VMEM-resident; return the plan when it fits."""
    plan = episode_vmem_plan(
        steps=steps, capacity=capacity, state_dim=state_dim,
        action_dim=action_dim, hidden=hidden, num_updates=num_updates,
        batch_size=batch_size, pad=pad, device_kind=device_kind,
        env_state_bytes=env_state_bytes)
    budget = plan["budget_bytes"]
    if plan["pipelined_bytes"] <= budget:
        return plan
    top = sorted(plan["contributions"].items(), key=lambda kv: -kv[1])[:3]
    detail = "; ".join(
        f"{name}={bytes_ / 2 ** 20:.2f} MiB ({_REMEDIES[name]})"
        for name, bytes_ in top)
    raise ValueError(
        f"megakernel episode does not fit in VMEM: chunk={chunk}, "
        f"steps={steps}, capacity={capacity}, space k={state_dim}/"
        f"m={action_dim} needs "
        f"{plan['pipelined_bytes'] / 2 ** 20:.2f} MiB per grid instance "
        f"(x{_PIPELINE_FACTOR} pipelining) against the "
        f"{budget / 2 ** 20:.2f} MiB budget of a {device_kind}. Top "
        f"contributors: {detail}. "
        f"Use the standard scan engine (REPRO_MEGAKERNEL=off) for this "
        f"configuration, or shrink the named knobs.")


def suggest_max_capacity(*, steps, state_dim, action_dim, hidden,
                         num_updates, batch_size, pad, device_kind,
                         env_state_bytes=0):
    """Largest replay capacity that still fits — the error message's main
    remedy, computed rather than guessed."""
    base = episode_vmem_plan(
        steps=steps, capacity=0, state_dim=state_dim,
        action_dim=action_dim, hidden=hidden, num_updates=num_updates,
        batch_size=batch_size, pad=pad, device_kind=device_kind,
        env_state_bytes=env_state_bytes)
    fixed = base["per_session_bytes"]
    per_row = (2 * int(state_dim) + int(action_dim) + 1) * 4
    headroom = base["budget_bytes"] // _PIPELINE_FACTOR - fixed
    return max(0, math.floor(headroom / per_row))
