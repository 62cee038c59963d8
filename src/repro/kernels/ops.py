"""Kernel dispatch layer: every hot spot has a Pallas TPU kernel and a pure-XLA
fallback; selection is automatic (TPU backend -> kernel) and overridable.
The one exception is the fused DDPG learner: ``auto`` runs its XLA scan on
every platform, which is faster on a TPU too (``ddpg_kernel_mode``).

    REPRO_KERNELS=xla        force the XLA (jnp) paths everywhere
    REPRO_KERNELS=pallas     force the Pallas kernels (compiled), the
                             learner's included
    REPRO_KERNELS=interpret  force the Pallas kernels in interpret mode (CPU
                             correctness testing — this is what the test
                             sweeps use)

The dry-run/roofline pipeline runs on the CPU backend and therefore measures
the XLA paths; that is the honest choice — cost_analysis of an opaque custom
call would count zero FLOPs for exactly the ops we care about.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.ddpg_fused import ddpg_fused_learn as _ddpg_fused_learn
from repro.kernels.ddpg_fused import ddpg_fused_xla as _ddpg_fused_xla
from repro.kernels.episode_fused import episode_fused_learn as _episode_learn
from repro.kernels.episode_fused import episode_fused_xla as _episode_xla
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gmm import gmm as _gmm
from repro.kernels.mamba2_scan import ssd_scan as _ssd_scan
from repro.kernels.rwkv6 import wkv6_scan as _wkv6_scan


def auto_mode(platform: str) -> str:
    """What ``REPRO_KERNELS=auto`` (the default) picks on ``platform`` for
    every kernel but the DDPG learner (``ddpg_kernel_mode``): the compiled
    Pallas kernels on a TPU, the XLA paths elsewhere."""
    return "pallas" if platform == "tpu" else "xla"


def _mode() -> str:
    m = os.environ.get("REPRO_KERNELS", "auto")
    return auto_mode(jax.default_backend()) if m == "auto" else m


# ---------------------------------------------------------------------------
# Fused DDPG inner loop (the tuning hot path — paper Table III)
# ---------------------------------------------------------------------------

def ddpg_kernel_mode():
    """'pallas' / 'interpret' when ``REPRO_KERNELS`` names the fused DDPG
    learner kernel; ``None`` otherwise, ``auto`` included on every platform,
    so the XLA learner (``core.ddpg``'s scan over ``_ddpg_step``) runs.
    Under the fleet's vmap that scan batches every session's matmuls into
    one dot, where the kernel's grid runs one session per step: on a TPU
    v5e, 256 magpie8 sessions x 96 updates took 45.7 ms against the
    kernel's 121.5 ms. ``core.ddpg._learn_scan`` consults this before
    packing parameters for the kernel."""
    m = os.environ.get("REPRO_KERNELS", "auto")
    return m if m in ("pallas", "interpret") else None


def ddpg_inner_loop(packed, batches, *, dims, gamma, tau, actor_lr,
                    critic_lr, mode=None):
    """Whole ``updates_per_step`` DDPG inner loop on the packed layout.

    Pallas kernel (params resident in VMEM across all updates, grid over the
    fleet session axis) under ``pallas``/``interpret``; otherwise the XLA
    twin of the same blocked computation (``ddpg_fused_xla``). Inputs follow
    ``kernels.ddpg_fused.pack_params`` / ``pack_minibatches``, every array
    carrying a leading fleet axis.

    ``mode`` defaults to ``ddpg_kernel_mode()`` — but callers that
    sit inside a jit trace must resolve ``ddpg_kernel_mode()`` on the host
    and pass it explicitly (a cached compilation would otherwise pin the
    first call's mode forever; ``core.ddpg`` threads it as a static operand).
    """
    mode = ddpg_kernel_mode() if mode is None else mode
    if mode in ("pallas", "interpret"):
        return _ddpg_fused_learn(
            packed, batches, dims=dims, gamma=gamma, tau=tau,
            actor_lr=actor_lr, critic_lr=critic_lr,
            interpret=mode == "interpret")
    return _ddpg_fused_xla(packed, batches, dims=dims, gamma=gamma, tau=tau,
                           actor_lr=actor_lr, critic_lr=critic_lr)


# ---------------------------------------------------------------------------
# Whole-episode megakernel (act -> env -> reward -> store -> inner loop)
# ---------------------------------------------------------------------------

_MEGAKERNEL_MODES = ("xla", "pallas", "interpret")


def episode_kernel_mode():
    """Resolve ``REPRO_MEGAKERNEL``: ``None`` (unset/``off``/``0``/``none``)
    keeps the standard scan engine — ``core.episode._compiled_episode`` keys
    on this value, so ``None`` compiles the exact pre-megakernel program.
    ``xla``/``pallas``/``interpret`` select the whole-episode fused
    formulation; ``auto`` means the Pallas kernel on TPU and the XLA twin
    elsewhere. Host-resolved only — never call this inside a jit trace."""
    m = os.environ.get("REPRO_MEGAKERNEL", "off").strip().lower()
    if m in ("", "off", "0", "none"):
        return None
    if m == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if m not in _MEGAKERNEL_MODES:
        raise ValueError(
            f"REPRO_MEGAKERNEL={m!r}: expected one of "
            f"{('off', 'auto') + _MEGAKERNEL_MODES}")
    return m


def episode_inner_loop(operands, *, spec, mode=None):
    """Whole chunk of T-step episodes in one fused program.

    ``pallas``/``interpret`` run the megakernel (one grid instance per
    session, every stateful operand VMEM-resident and aliased across the
    call); ``xla`` runs the identical per-session body vmapped. Inputs
    follow ``kernels.episode_fused.EpisodeOperands``; like
    ``ddpg_inner_loop``, jit-traced callers must resolve the mode on the
    host and pass it explicitly."""
    mode = episode_kernel_mode() if mode is None else mode
    if mode in ("pallas", "interpret"):
        return _episode_learn(operands, spec=spec,
                              interpret=mode == "interpret")
    return _episode_xla(operands, spec=spec)


# ---------------------------------------------------------------------------
# Flash attention: q [B,S,H,D]; k/v [B,S,Kv,D] (model-layout) -> [B,S,H,D]
# ---------------------------------------------------------------------------

def attention(q, k, v, causal: bool = True):
    mode = _mode()
    S = q.shape[1]
    usable = S % 128 == 0 and k.shape[1] % 128 == 0 and q.shape[-1] >= 8
    if mode in ("pallas", "interpret") and usable:
        qt = jnp.swapaxes(q, 1, 2)      # [B,H,S,D]
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        o = _flash(qt, kt, vt, causal, 128, 128, mode == "interpret")
        return jnp.swapaxes(o, 1, 2)
    from repro.models.attention import sdpa
    Sq, Sk = q.shape[1], k.shape[1]
    impl = "chunked" if (Sq * Sk > 4096 * 4096 and Sq % 512 == 0
                         and Sk % 512 == 0) else "ref"
    return sdpa(q, k, v, causal=causal, impl=impl)


# ---------------------------------------------------------------------------
# Mamba2 SSD: x [b,s,h,p], dt [b,s,h], A [h], Bm/Cm [b,s,n]
# ---------------------------------------------------------------------------

def ssd(x, dt, A, Bm, Cm, chunk: int):
    mode = _mode()
    b, s, h, p = x.shape
    if mode in ("pallas", "interpret") and s % chunk == 0:
        xf = jnp.swapaxes(x, 1, 2).reshape(b * h, s, p)
        dtf = jnp.swapaxes(dt, 1, 2).reshape(b * h, s)
        Af = jnp.broadcast_to(A[None, :], (b, h)).reshape(b * h)
        y, state = _ssd_scan(xf, dtf, Af, Bm, Cm, heads=h, chunk=chunk,
                             interpret=mode == "interpret")
        y = jnp.swapaxes(y.reshape(b, h, s, p), 1, 2)
        n = Bm.shape[-1]
        return y, state.reshape(b, h, n, p)
    from repro.models.ssm import ssd_chunked
    return ssd_chunked(x, dt, A, Bm, Cm, chunk)


# ---------------------------------------------------------------------------
# RWKV6 WKV: r/k/v/logw [B,S,H,c], u [H,c]
# ---------------------------------------------------------------------------

def wkv6(r, k, v, logw, u, chunk: int = 64):
    mode = _mode()
    B, S, H, c = r.shape
    if mode in ("pallas", "interpret") and S % chunk == 0:
        def fold(t):
            return jnp.swapaxes(t, 1, 2).reshape(B * H, S, c)
        uf = jnp.broadcast_to(u[None], (B, H, c)).reshape(B * H, c)
        y, state = _wkv6_scan(fold(r), fold(k), fold(v), fold(logw), uf,
                              chunk=chunk, interpret=mode == "interpret")
        y = jnp.swapaxes(y.reshape(B, H, S, c), 1, 2)
        return y, state.reshape(B, H, c, c)
    from repro.models.rwkv import wkv_chunked
    return wkv_chunked(r, k, v, logw, u, min(32, S))


# ---------------------------------------------------------------------------
# Grouped matmul / grouped SwiGLU (MoE experts)
# ---------------------------------------------------------------------------

def grouped_matmul(x, w):
    mode = _mode()
    E, C, D = x.shape
    F = w.shape[-1]
    aligned = C % 128 == 0 and D % 128 == 0 and F % 128 == 0
    if mode in ("pallas", "interpret") and aligned:
        return _gmm(x, w, interpret=mode == "interpret")
    return jnp.einsum("ecd,edf->ecf", x, w)


def grouped_swiglu(x, w_gate, w_up, w_down):
    """[E,C,D] -> [E,C,D]: the MoE expert-FFN hot spot."""
    g = jax.nn.silu(grouped_matmul(x, w_gate))
    u = grouped_matmul(x, w_up)
    return grouped_matmul(g * u, w_down)
