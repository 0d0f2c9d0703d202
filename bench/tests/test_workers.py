"""The reference over W data-parallel workers: each worker on its rows of
the global batch, the FP32 leaves' mean and the voted leaves' sign of the
summed votes, 0 on a tie.  A whole run of a four-worker cell on four CPU
devices is correct, and comes out not correct without the exchange between
workers, or with another fault planted; one worker reads as before."""
import hashlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, CPU_PEAKS, TINY4_LIMITS, TINY4_TRAFFIC, \
    TINY_CONFIG, write_root
from harness import cell as C
from harness import checks, spec, traffic
from reference import common
from reference import model as R

CELL = "tiny.gbin_packed.4"


def _run(root, fault=None):
    cell = spec.find_cell(CELL, root)
    return C.run(cell, 2**31 + 5, 0.5, False, t_start=time.time(),
                 require_tpu=False, peaks=CPU_PEAKS, fault=fault)


def test_four_worker_run_is_correct(four_root):
    r = _run(four_root)
    assert r["correct"], r["checks"]
    assert r["device"]["count"] == 4


@pytest.mark.parametrize("fault", ["no_exchange", "state_unchanged",
                                   "half_batch"])
def test_four_worker_fault_is_caught(four_root, fault):
    r = _run(four_root, fault)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("seed", [23, 2**31 + 24])
def test_four_worker_control_fails(four_root, seed):
    cell = spec.find_cell(CELL, four_root)
    pool = traffic.pool_for(cell, seed)
    ref = C.reference_readings(cell, seed, pool, 3)
    ctl = C.reference_readings(cell, seed, pool, 3, matmul="fp8")
    ctl["paths"] = ref["paths"]
    rows = checks.judge(C.compare(ctl, ref, C.voted_rule(cell)),
                        TINY4_LIMITS)
    assert not all(r["ok"] for r in rows), rows


def test_program_matches_the_reference_on_four_workers(tmp_path):
    """At float32 the program's checked steps on four workers and the
    reference's agree to rounding: the same rows, votes, ties and mean."""
    config = dict(TINY_CONFIG, torch_dtype="float32")
    root = write_root(tmp_path, {"tiny": config}, {"t": TINY4_TRAFFIC},
                      [{"name": "c", "config": "tiny", "traffic": "t",
                        "chips": 4, "why": "t"}])
    cell = spec.find_cell("c", root)
    pool = traffic.pool_for(cell, 7)
    trainer = C.build(cell, 7, jax.devices()[:4])
    trainer.data = pool
    voted = C.voted_rule(cell)
    prog = C.program_readings(trainer, pool, 3, 0.9, voted)
    ref = C.reference_readings(cell, 7, pool, 3)
    vals = C.compare(prog, ref, voted)
    assert vals["loss_gap"] < 1e-5
    assert vals["grad_norm_gap"] < 1e-4
    assert vals["vote_mismatch"] < 2e-3
    assert vals["update_norm_gap"] < 2e-3
    assert vals["update_mismatch"] < 2e-3


def test_workers_take_their_rows_and_ties_vote_zero():
    """The four-worker first aggregate against four one-worker gradients,
    each of one block of rows, aggregated by hand."""
    config = dict(TINY_CONFIG, torch_dtype="float32")
    voted = common.voted_rule("gbin_packed")
    opt = common.AdamW(3e-4, 100, 1000)
    ref = common.Reference(R, config, opt, voted=voted,
                           devices=jax.devices()[:4])
    rng = np.random.default_rng(3)
    batch = {k: rng.integers(0, 512, (8, 16)).astype(np.int32)
             for k in ("tokens", "labels")}
    out = ref.steps(5, [batch])

    s = ref.s
    w = R.init_weights(5, s)
    w32 = jax.tree.map(lambda x: x.astype(jnp.float32), w)
    grad = jax.jit(jax.grad(lambda p, t, y: R.loss(p, t, y, s)))
    with jax.default_matmul_precision("highest"):
        per = [jax.tree.leaves(grad(w32, batch["tokens"][2 * i:2 * i + 2],
                                    batch["labels"][2 * i:2 * i + 2]))
               for i in range(4)]
    ties = 0
    bits = iter(out["bits"])
    for k, p in enumerate(out["paths"]):
        gs = np.stack([np.asarray(g[k]) for g in per])
        if voted(p):
            total = np.where(gs > 0, 1, -1).sum(axis=0)
            ties += int(np.sum(total == 0))
            want = np.sign(total)
            pos, neg = next(bits)
            got = (np.unpackbits(pos)[:want.size].astype(int)
                   - np.unpackbits(neg)[:want.size].astype(int))
            assert np.mean(got != want.reshape(-1)) < 1e-3, p
        else:
            want = gs.mean(axis=0)
        assert out["agg_norm"][k] == pytest.approx(
            float(np.linalg.norm(want)), rel=1e-4), p
    assert ties > 0


def test_one_worker_reference_is_unchanged(tiny_root):
    """One worker computes what the reference computed before its shared
    parts moved out of ``model.py``: the readings recorded then, equal."""
    want = json.loads((BENCH / "tests" / "data"
                       / "reference_w1_tiny.json").read_text())
    cell = spec.find_cell("tiny.gbin_packed", tiny_root)
    seed = 2**31 + 7
    pool = traffic.pool_for(cell, seed)

    def digest(pairs):
        return hashlib.sha256(b"".join(a.tobytes() + b.tobytes()
                                       for a, b in pairs)).hexdigest()

    for matmul in ("highest", "fp8"):
        got = C.reference_readings(cell, seed, pool, 3, matmul=matmul)
        w = want[matmul]
        for k in ("loss", "agg_norm", "raw_norm", "change_norm", "paths",
                  "voted_sizes"):
            assert got[k] == w[k], (matmul, k)
        assert digest(got["bits"]) == w["bits"], matmul
        assert digest(got["change_bits"]) == w["change_bits"], matmul
