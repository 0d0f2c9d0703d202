"""What every plain reference shares: the optimizer, the aggregation over W
data-parallel workers, the readings, and the loop over the checked steps.

A configuration names its reference with ``"reference": "<module>"``
(``model`` when it names none): the file ``bench/reference/<module>.py``,
which ``harness.spec.reference_module`` loads.  Such a module imports
nothing of the program and provides

* ``spec_of(config) -> Spec``: the sizes it needs, read from the
  configuration file, as a hashable value;
* ``init_weights(seed, spec) -> tree``: the initialisation the model
  states, from ``seed`` (an int or a PRNG key), in the storage dtypes,
  with the leaf paths of the program's parameters;
* ``loss(w32, tokens, labels, spec, *, matmul) -> scalar``: the mean
  next-token cross entropy of one worker's rows in float32, every matmul
  taken through ``einsum_for(matmul)``, so that ``matmul="fp8"`` computes
  the lower-precision control;

and, where the dense decoder's formulas of ``harness.flops`` do not count
its model, its own

* ``model_flops_per_token(config, seq_len) -> float``: training FLOPs
  per token, forward and backward, no recompute;
* ``admitted_elements(config) -> int``: the elements the low-bit
  backbone votes on.

A new architecture's reference is those functions in one new file; this
module does the rest for every one of them.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

#: float8 e4m3 as ``lax.reduce_precision`` rounds it (4 exponent bits, 3
#: mantissa bits, IEEE-style: the largest finite value is 240)
F8_EXP, F8_MANT, F8_MAX = 4, 3, 240.0

#: the mesh axis of the reference's workers
WORKERS = "w"


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (straight-through)."""
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX)
    q = jax.lax.reduce_precision(x / scale, exponent_bits=F8_EXP,
                                 mantissa_bits=F8_MANT) * scale
    return x + jax.lax.stop_gradient(q - x)


def einsum_for(matmul: str):
    """``einsum`` at ``precision="highest"``; with ``matmul="fp8"`` on
    operands rounded to float8 e4m3 (the control)."""
    if matmul == "highest":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision="highest")
    if matmul == "fp8":
        return lambda eq, a, b: jnp.einsum(eq, fp8(a), fp8(b),
                                           precision="highest")
    raise ValueError(f"unknown matmul precision {matmul!r}")


#: a leaf takes the FP32 mean where a whole segment of its path is one of
#: these: the head and the MoE routers, whose gradients are small and
#: decide the output
FP32_SEGMENTS = frozenset({"head", "lm_head", "classifier", "logits",
                           "router", "gate_weight", "gate_weights"})

#: ... or where its path holds one of these anywhere: norms, biases and
#: scales, and the embedding and input projections
FP32_SUBSTRINGS = ("norm", "bias", "scale", "ln_", "layernorm",
                   "embed", "wte", "wpe", "patch_proj", "frontend")


def on_fp32_path(path: str) -> bool:
    """Whether the low-bit backbone plan keeps the leaf at ``path`` on the
    FP32 mean.  A list index may print as ``0`` or ``[0]``: either is a
    segment of its own and matches nothing."""
    return (not FP32_SEGMENTS.isdisjoint(path.split("/"))
            or any(w in path for w in FP32_SUBSTRINGS))


def voted_rule(plan: str):
    """Which leaves a plan puts on the sign vote, by leaf path.

    ``gbin_packed`` votes every leaf but those :func:`on_fp32_path` keeps
    on the FP32 mean; ``fp32`` votes none.
    """
    if plan == "fp32":
        return lambda path: False
    if plan == "gbin_packed":
        return lambda path: not on_fp32_path(path)
    raise ValueError(f"the reference knows no plan {plan!r}")


def path_of(key_path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in key_path)


def leaf_paths(tree) -> list[str]:
    return [path_of(kp) for kp, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@dataclasses.dataclass(frozen=True)
class AdamW:
    peak_lr: float
    warmup_steps: int
    total_steps: int
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    min_ratio: float = 0.1

    def lr(self, t):
        t = jnp.asarray(t, jnp.float32)
        warm = t / max(self.warmup_steps, 1)
        prog = jnp.clip((t - self.warmup_steps)
                        / max(self.total_steps - self.warmup_steps, 1), 0, 1)
        cos = self.min_ratio + (1 - self.min_ratio) * 0.5 * (
            1 + jnp.cos(jnp.pi * prog))
        return self.peak_lr * jnp.where(t < self.warmup_steps, warm, cos)


def leaf_norm(x) -> jax.Array:
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(x * x))


def vote_bits(u) -> tuple:
    """Packed (u > 0, u < 0) bits of a leaf: the signs of a vote, or the
    direction of a change."""
    flat = u.reshape(-1)
    return jnp.packbits(flat > 0), jnp.packbits(flat < 0)


def change_readings(after, before) -> tuple:
    """Per leaf, the norm and the direction bits of ``after - before``."""
    d = [a.astype(jnp.float32) - b.astype(jnp.float32)
         for a, b in zip(after, before)]
    return [leaf_norm(x) for x in d], [vote_bits(x) for x in d]


def aggregate(g, on_vote: bool, axis=None, workers: int = 1):
    """One leaf's aggregate over the workers of mesh axis ``axis`` (one
    worker where None).

    On the FP32 path the mean gradient (float32).  On the vote each
    worker votes +1 where its gradient is > 0, else -1, and the aggregate
    is the sign of the sum of the votes, 0 on a tie (int8): the program's
    margin a = 2c - W of c votes for +1, kept where a != 0.
    """
    if not on_vote:
        return g if axis is None else jax.lax.psum(g, axis) / workers
    v = jnp.where(g > 0, 1, -1).astype(jnp.int8)
    if axis is None:
        return v
    return jnp.sign(jax.lax.psum(v.astype(jnp.int32), axis)).astype(jnp.int8)


class Reference:
    """The first steps of the data-parallel workers of a reference model.

    ``arch`` is the reference module (see this module's docstring).  With
    ``devices`` (one per worker) the W workers take the rows of each
    global batch in order, W equal blocks, as the program's ``data``
    sharding gives them; their gradients are computed at once, one worker
    to a device, and aggregated before AdamW.  With one worker (``devices``
    None or one device) the step runs as one jitted program on the default
    device.
    """

    def __init__(self, arch, config: dict, opt: AdamW, *, voted,
                 matmul: str = "highest", devices=None):
        from jax.sharding import Mesh
        self.arch = arch
        self.s = arch.spec_of(config)
        self.voted = voted
        self.opt = opt
        self.matmul = matmul
        self.mesh = (Mesh(np.asarray(devices), (WORKERS,))
                     if devices is not None and len(devices) > 1 else None)
        self.workers = 1 if self.mesh is None else self.mesh.size

    def _sharding(self, spec):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(*spec))

    def _jit(self, fn, **kwargs):
        """``jax.jit``, its outputs replicated over the workers' mesh."""
        if self.mesh is not None:
            kwargs["out_shardings"] = self._sharding(())
        return jax.jit(fn, **kwargs)

    def _rows(self, x):
        """A global batch's array, split by rows over the workers."""
        x = np.asarray(x)
        if self.mesh is None:
            return x
        return jax.device_put(x, self._sharding((WORKERS,)))

    def init(self, seed: int):
        with jax.default_matmul_precision("highest"):
            return self._jit(lambda k: self.arch.init_weights(k, self.s))(
                jax.random.PRNGKey(seed))

    def grad_fn(self, w0):
        """Jitted (w, tokens, labels, first) -> (agg, readings).

        ``agg`` holds each leaf's aggregate over the workers (see
        :func:`aggregate`).  With ``first`` (static) the readings of the
        first step come too: per leaf the norms of the aggregate and of the
        mean raw gradient, and the vote bits.
        """
        s = self.s
        treedef = jax.tree_util.tree_structure(w0)
        paths = leaf_paths(w0)
        vg = jax.value_and_grad(
            lambda w, t, y: self.arch.loss(w, t, y, s, matmul=self.matmul))
        axis = None if self.mesh is None else WORKERS
        workers = self.workers

        def fn(w, tokens, labels, first: bool):
            w32 = [x.astype(jnp.float32) for x in w]
            tree = jax.tree_util.tree_unflatten(treedef, w32)
            lossv, grads = vg(tree, tokens, labels)
            if axis is not None:
                lossv = jax.lax.pmean(lossv, axis)
            agg, out = [], {"loss": lossv}
            raw_norm, agg_norm, bits = [], [], []
            for p, g in zip(paths, jax.tree_util.tree_leaves(grads)):
                a = aggregate(g, self.voted(p), axis, workers)
                if self.voted(p):
                    bits.append(vote_bits(a))
                agg.append(a)
                raw_norm.append(leaf_norm(
                    g if axis is None else jax.lax.pmean(g, axis)))
                agg_norm.append(leaf_norm(a))
            if first:
                out.update(raw_norm=raw_norm, agg_norm=agg_norm, bits=bits)
            return agg, out

        if axis is None:
            return jax.jit(fn, static_argnums=(3,))
        from jax.sharding import PartitionSpec as P

        def sharded(w, tokens, labels, first: bool):
            # unchecked, so that each worker's gradient stays its own: the
            # checked form would sum the gradients of the replicated weights
            # over the workers before the aggregation sees them
            return jax.shard_map(
                lambda w, t, y: fn(w, t, y, first), mesh=self.mesh,
                in_specs=(P(), P(axis), P(axis)), out_specs=P(),
                check_vma=False)(w, tokens, labels)

        return jax.jit(sharded, static_argnums=(3,))

    def update_fn(self):
        """Jitted AdamW: (w, m, v, agg, t) -> (w, m, v), the weights in
        their storage dtypes: the update is taken in float32 and cast back,
        so a bfloat16 weight moves only where it crosses a rounding step."""
        opt = self.opt

        def fn(w, m, v, agg, t):
            lr = opt.lr(t)
            out_w, out_m, out_v = [], [], []
            for w_i, g, mi, vi in zip(w, agg, m, v):
                x = w_i.astype(jnp.float32)
                g = g.astype(jnp.float32)
                mi = opt.b1 * mi + (1 - opt.b1) * g
                vi = opt.b2 * vi + (1 - opt.b2) * g * g
                mhat = mi / (1 - opt.b1 ** t)
                vhat = vi / (1 - opt.b2 ** t)
                x = x - lr * mhat / (jnp.sqrt(vhat) + opt.eps)
                out_w.append(x.astype(w_i.dtype))
                out_m.append(mi)
                out_v.append(vi)
            return out_w, out_m, out_v

        return self._jit(fn, donate_argnums=(0, 1, 2, 3))

    def steps(self, seed: int, batches: list[dict]) -> dict:
        """Run ``len(batches)`` steps from the weights of ``seed``.

        Each batch holds the global batch's ``tokens`` and ``labels``.
        Returns losses (the mean over the workers), per-leaf norms of the
        first step's aggregate and mean raw gradient, the first step's vote
        bits, and per leaf the norm and the direction bits of the
        parameters' change over all the steps.  The initial weights are
        drawn again for the change rather than kept, to fit.
        """
        zeros = self._jit(lambda xs: [jnp.zeros(x.shape, jnp.float32)
                                      for x in xs])
        with jax.default_matmul_precision("highest"):
            w0 = self.init(seed)
            paths = leaf_paths(w0)
            gjit, ujit = self.grad_fn(w0), self.update_fn()
            w = jax.tree_util.tree_leaves(w0)
            del w0
            m, v = zeros(w), zeros(w)
            result = {"loss": []}
            for i, b in enumerate(batches):
                agg, out = gjit(w, self._rows(b["tokens"]),
                                self._rows(b["labels"]), i == 0)
                result["loss"].append(float(out["loss"]))
                if i == 0:
                    result["agg_norm"] = [float(x) for x in out["agg_norm"]]
                    result["raw_norm"] = [float(x) for x in out["raw_norm"]]
                    result["bits"] = [(np.asarray(a), np.asarray(b_))
                                      for a, b_ in out["bits"]]
                del out
                w, m, v = ujit(w, m, v, agg, jnp.float32(i + 1))
            del m, v
            w0 = jax.tree_util.tree_leaves(self.init(seed))
            norm, bits = jax.jit(change_readings)(w, w0)
            result["change_norm"] = [float(x) for x in norm]
            result["change_bits"] = [(np.asarray(p), np.asarray(n))
                                     for p, n in bits]
        result["paths"] = paths
        return result
