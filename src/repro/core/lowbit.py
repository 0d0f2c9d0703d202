"""Distributed low-bit gradient aggregation collectives (the paper's core).

Every function here runs *inside* a ``jax.shard_map`` whose manual axes are
the data-parallel mesh axes (``('pod', 'data')`` on the production mesh):
per-device gradients are visible before reduction, which is the JAX
analogue of the paper's premise that the controller sees per-worker
payloads rather than an already-reduced tensor.

Semantics (paper Section 2, identical across schedules):

    b_{k,i} = 1{ sgn(g_{k,i}) > 0 }
    c_i     = PopCount_k(b_{k,i})           (vote count over W workers)
    a_i     = 2 c_i - W                      (vote margin)
    u_i     = sgn(a_i)                       (G-Binary)
    u_i     = m_i * sgn(a_i)                 (G-Ternary, 2-of-3 zero gate)

Two schedules implement the same semantics with different bytes-on-wire:

  * ``vote_psum``   — int8 sign votes, one ``psum`` over the DP axes.
                      ~2N bytes/device modeled (vs ~8N for FP32 ring
                      all-reduce); the XLA realization widens the psum
                      operand to int32 so the margin stays exact at any W.
  * ``packed_a2a``  — the controller schedule.  Workers pack sign bits
                      (``sign_pack`` kernel, N/8 bytes), ``all_to_all``
                      routes each packed shard to the device that "owns"
                      that element range (the write into the CXL-resident
                      buffer), the owner runs the PopCount/majority Pallas
                      datapath, and the packed ternary result is
                      all-gathered back (the read response).
                      ~(N/8 + N/4) bytes/device: ~21x less than FP32.

FP32 aggregation stays available per bucket (``fp32_allreduce``), exactly
as the paper's bypass path.  ``sign_of_mean`` and ``majority_sign_sgd``
are the paper's Section 9 baselines.

Beyond the paper: optional per-worker error feedback (EF-signSGD style)
on the vote input, which tightens the hard-workload boundary (see
EXPERIMENTS.md) at the cost of one residual buffer per admitted bucket.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import kernels as K
from ..kernels import fused as KF
from .modes import AggregationMode, Schedule

Axes = Sequence[str] | str


# ---------------------------------------------------------------------------
# FP32 bypass path
# ---------------------------------------------------------------------------

def fp32_allreduce(g: jax.Array, dp_axes: Axes) -> jax.Array:
    """Full-precision mean aggregate (the calibration / recovery path).

    The collective runs on an FP32 payload regardless of the gradient's
    storage dtype — this *is* the paper's FP32 bypass semantics, and it is
    what the wire-byte accounting (4 bytes/element) assumes.
    """
    return jax.lax.pmean(g.astype(jnp.float32), dp_axes)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _flat_index_gate(shape, phase: int, dtype=jnp.float32) -> jax.Array:
    """Fixed 2-of-3 zero gate over flattened elements (paper Section 2)."""
    n = 1
    for s in shape:
        n *= s
    idx = jnp.arange(n).reshape(shape)
    return (((idx + phase) % 3) != 2).astype(dtype)


def _ef_inject(g: jax.Array, ef: jax.Array | None):
    """Error-feedback vote input: votes are taken on g + e (beyond paper)."""
    if ef is None:
        return g, None
    return g + ef.astype(g.dtype), ef


def _ef_update(g_eff: jax.Array, ef: jax.Array | None):
    """Residual update e' = x - beta * sgn(x), beta = mean|x| (EF-signSGD)."""
    if ef is None:
        return None
    beta = jnp.mean(jnp.abs(g_eff))
    sent = beta * jnp.sign(g_eff)
    return (g_eff - sent).astype(ef.dtype)


# ---------------------------------------------------------------------------
# vote_psum schedule (dense int8 votes)
# ---------------------------------------------------------------------------

def lowbit_vote_psum(g: jax.Array, dp_axes: Axes, num_workers: int, *,
                     ternary: bool = False, gate_phase: int = 0,
                     ef: jax.Array | None = None,
                     gate: jax.Array | None = None):
    """Sign votes as int8, one psum over DP, majority (+ optional gate).

    Works on arbitrarily sharded leaves (pure elementwise + psum), so it is
    the default schedule for tensor-parallel-sharded parameters.

    ``gate`` optionally overrides the flat-index 2-of-3 gate with an
    explicit {0, 1} keep vector — the fused bucket path passes the
    concatenation of per-leaf gates here.

    Returns ``(u, new_ef)`` with ``u`` in {-1, 0, +1} (dtype of ``g``).
    """
    g_eff, ef = _ef_inject(g, ef)
    votes = jnp.where(g_eff > 0, jnp.int8(1), jnp.int8(-1))
    # The *accumulation* must be wider than the 1-byte vote: the margin
    # a_i = 2c - W spans [-W, W], which wraps int8 for W >= 128.  Note
    # the XLA realization therefore ships the widened int32 operand; the
    # schedule's wire-byte model keeps counting the paper's logical
    # 1-byte vote payload (what a controller-side popcount would move) —
    # see VotePsumBackend.wire_bytes_per_device.
    margin = jax.lax.psum(votes.astype(jnp.int32), dp_axes)
    u = jnp.sign(margin.astype(jnp.float32))
    if ternary:
        u = u * (_flat_index_gate(g.shape, gate_phase) if gate is None
                 else gate.astype(u.dtype))
    return u.astype(g.dtype), _ef_update(g_eff, ef)


# ---------------------------------------------------------------------------
# packed_a2a schedule (the controller datapath on ICI)
# ---------------------------------------------------------------------------

def _packed_a2a_local(g: jax.Array, dp_axes: Axes, num_workers: int, *,
                      ternary: bool, gate_phase: int,
                      ef: jax.Array | None, interpret: bool | None,
                      gate_mask=None, kernels: KF.KernelSet | None = None):
    """Packed aggregation over DP for a *fully local* array.

    ``gate_mask`` (host-side boolean (N,) array) overrides the uniform
    flat-index 2-of-3 gate with an arbitrary keep pattern; the fused
    bucket path uses it to carry the concatenation of per-leaf gates.
    ``kernels`` (a vote-capable :class:`~repro.kernels.fused.KernelSet`)
    reroutes the whole chain to the codec's fused kernels — bit-identical
    by the KernelSet contract, fewer launches and no intermediate HBM
    materialization.
    """
    if kernels is not None and kernels.votes:
        return kernels.packed_vote(g, dp_axes, num_workers, ternary=ternary,
                                   gate_phase=gate_phase, ef=ef,
                                   interpret=interpret, gate_mask=gate_mask)
    w = num_workers
    n = g.size
    g_eff, ef = _ef_inject(g, ef)
    plane = K.to_plane(g_eff.reshape(-1))
    words = K.pack_signs(plane, interpret=interpret)      # (R, 128) u32
    r = words.shape[0]
    rw = K.shard_rows(r, w)
    pad_r = rw * w - r
    if pad_r:
        words = jnp.pad(words, ((0, pad_r), (0, 0)))
    # "write-side materialization": route worker payloads to the owning
    # aggregator for each element range.
    routed = jax.lax.all_to_all(words.reshape(w, rw, K.LANE), dp_axes,
                                split_axis=0, concat_axis=0, tiled=False)
    # "controller datapath": PopCount across workers + majority/ternary gate
    # (the gate helper is shared with the fused driver, so both pipelines
    # consume byte-identical zero gates by construction).
    counts = K.popcount_stack(routed, interpret=interpret)
    gate = KF.shard_gate_words(dp_axes, rw, ternary=ternary,
                               gate_phase=gate_phase, gate_mask=gate_mask,
                               total_rows=r + pad_r)
    sw, mw = K.majority_decode(counts, num_workers=w, gate_words=gate,
                               interpret=interpret)
    # "read response": packed ternary aggregate gathered back to all workers.
    sw_all = jax.lax.all_gather(sw, dp_axes, axis=0, tiled=True)[:r]
    mw_all = jax.lax.all_gather(mw, dp_axes, axis=0, tiled=True)[:r]
    u_plane = K.unpack_ternary(sw_all, mw_all, dtype=jnp.float32,
                               interpret=interpret)
    u = K.from_plane(u_plane, n).reshape(g.shape).astype(g.dtype)
    return u, _ef_update(g_eff, ef)


def fully_manual(fn, in_specs=P(), out_specs=P()):
    """``fn`` under a shard_map manual over every mesh axis still automatic.

    Mosaic kernels cannot be partitioned automatically, so every Pallas
    call in a train step runs where no mesh axis is left to the
    partitioner.  The train step's shard_map is manual over the DP axes
    only; this adds the remaining ones (``'model'``) for the span of
    ``fn``.  Specs default to replicated, so a leaf sharded over an auto
    axis is gathered first — the semantics of the un-sharded leaf.
    Outside any mesh (host-local sessions, vmapped virtual workers)
    ``fn`` is returned as is.
    """
    mesh = jax.sharding.get_abstract_mesh()
    rest = frozenset(a for a in mesh.axis_names if a not in mesh.manual_axes)
    if not rest:
        return fn
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                         axis_names=rest, check_vma=False)


def lowbit_packed_a2a(g: jax.Array, dp_axes: Axes, num_workers: int, *,
                      model_spec: P | None = None, ternary: bool = False,
                      gate_phase: int = 0, ef: jax.Array | None = None,
                      interpret: bool | None = None, gate_mask=None,
                      kernels: KF.KernelSet | None = None):
    """Controller-schedule aggregation.

    The datapath runs under :func:`fully_manual`.  If the leaf is sharded
    over auto (tensor-parallel) mesh axes, ``model_spec`` must give its
    PartitionSpec, and each shard votes on its own slice.  ``gate_mask``
    (fully local payloads only) overrides the flat-index ternary gate —
    see :func:`_packed_a2a_local`.  ``kernels`` routes the chain to the
    codec's fused kernel set when present.
    """
    kwargs = dict(ternary=ternary, gate_phase=gate_phase, interpret=interpret,
                  kernels=kernels, gate_mask=gate_mask)
    spec = P() if model_spec is None else model_spec
    assert gate_mask is None or all(a is None for a in spec), \
        "gate_mask requires a fully local payload"

    if ef is None:
        def inner(gl):
            u, _ = _packed_a2a_local(gl, dp_axes, num_workers, ef=None, **kwargs)
            return u
        return fully_manual(inner, spec, spec)(g), None

    def inner_ef(gl, efl):
        return _packed_a2a_local(gl, dp_axes, num_workers, ef=efl, **kwargs)
    return fully_manual(inner_ef, (spec, spec), (spec, spec))(g, ef)


# ---------------------------------------------------------------------------
# Section 9 baselines
# ---------------------------------------------------------------------------

def majority_sign_sgd(g: jax.Array, dp_axes: Axes, num_workers: int):
    """MajoritySignSGD: communication-comparable software sign baseline.

    Identical update rule to G-Binary (each worker contributes a sign; the
    majority decides); kept separate because the paper positions it as the
    software reference against the controller-resident primitive.
    """
    u, _ = lowbit_vote_psum(g, dp_axes, num_workers)
    return u


def sign_of_mean(g: jax.Array, dp_axes: Axes) -> jax.Array:
    """SignOfMean: sign taken *after* the FP32 mean (optimizer reference).

    Not communication-comparable — the full-precision reduction has already
    happened (paper Section 2, "Sign-gradient baselines").
    """
    return jnp.sign(jax.lax.pmean(g, dp_axes)).astype(g.dtype)


# ---------------------------------------------------------------------------
# per-leaf dispatch
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LeafPolicy:
    """Resolved aggregation policy for one gradient leaf.

    ``mode`` names the gradient codec (a built-in
    :class:`AggregationMode` member or the string name of any codec
    registered via ``repro.fabric.register_codec``); ``schedule`` may be
    a built-in :class:`Schedule` member or the string name of any
    backend registered via ``repro.fabric.register_schedule``.
    """
    mode: AggregationMode | str
    schedule: Schedule | str
    model_spec: Any = None          # PartitionSpec over auto (TP) axes
    gate_phase: int = 0
    error_feedback: bool = False


def aggregate_leaf(g: jax.Array, policy: LeafPolicy, dp_axes: Axes,
                   num_workers: int, ef: jax.Array | None = None,
                   interpret: bool | None = None):
    """Deprecated free-function shim — use ``repro.fabric``.

    Dispatches through the schedule-backend registry (no hardcoded
    mode/schedule branching lives here anymore).  Returns
    ``(aggregate, new_ef)``; for FP32 the aggregate is the mean gradient,
    for low-bit modes it is the ternary direction in {-1, 0, +1}.
    """
    from ..fabric import AggregationContext
    from ..fabric.session import aggregate_leaf as _dispatch
    ctx = AggregationContext(dp_axes=dp_axes, num_workers=num_workers,
                             interpret=interpret)
    return _dispatch(ctx, g, policy, ef=ef)
