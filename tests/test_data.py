"""Data pipeline: determinism, host sharding, learnability, prefetch."""
import numpy as np

from repro.data import (MarkovLM, Prefetcher, SyntheticLMStream,
                        make_cluster_task)


def test_stream_deterministic_per_step():
    a = SyntheticLMStream(vocab=64, seq_len=16, batch=4, seed=3)
    b = SyntheticLMStream(vocab=64, seq_len=16, batch=4, seed=3)
    for step in (0, 5, 17):
        np.testing.assert_array_equal(a.batch_at(step)["tokens"],
                                      b.batch_at(step)["tokens"])
    assert not np.array_equal(a.batch_at(0)["tokens"],
                              a.batch_at(1)["tokens"])


def test_stream_host_sharding_differs():
    a = SyntheticLMStream(vocab=64, seq_len=16, batch=4, seed=3, host_index=0)
    b = SyntheticLMStream(vocab=64, seq_len=16, batch=4, seed=3, host_index=1)
    assert not np.array_equal(a.batch_at(0)["tokens"], b.batch_at(0)["tokens"])


def test_labels_are_shifted_tokens():
    s = SyntheticLMStream(vocab=64, seq_len=16, batch=4, seed=0)
    b = s.batch_at(0)
    # contract: labels[t] is the next token after tokens[t]
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_markov_chain_is_learnable():
    """Chain transitions are low-entropy: bigram statistics are skewed."""
    chain = MarkovLM(vocab=32, seed=0, topk=4)
    rng = np.random.RandomState(0)
    toks = chain.sample(rng, 64, 128)
    # successor sets are restricted to topk per token
    succ = {}
    for row in toks:
        for a, b in zip(row[:-1], row[1:]):
            succ.setdefault(int(a), set()).add(int(b))
    assert max(len(v) for v in succ.values()) <= 4


def test_markov_chain_builds_at_full_vocab():
    """qwen3's 151,936-token vocab: successors cost O(vocab * topk) host
    memory, stay distinct per token, and are deterministic per seed."""
    import time
    t0 = time.perf_counter()
    chain = MarkovLM(vocab=151936, seed=0)
    assert time.perf_counter() - t0 < 10.0
    assert chain.succ.shape == (151936, 16)
    assert chain.succ.min() >= 0 and chain.succ.max() < 151936
    assert (np.diff(chain.succ, axis=1) > 0).all()
    np.testing.assert_array_equal(chain.succ, MarkovLM(151936, seed=0).succ)


def test_prefetcher_yields_everything():
    it = iter([{"x": i} for i in range(7)])
    out = list(Prefetcher(it, depth=2))
    assert [o["x"] for o in out] == list(range(7))


def test_cluster_task_difficulty_knob():
    easy = make_cluster_task(10, hard=False, seed=0)
    hard = make_cluster_task(100, hard=True, seed=0)
    # easy clusters are farther apart relative to noise than hard ones
    def margin(task):
        c = task.centers
        d = np.linalg.norm(c[:, None] - c[None], axis=-1)
        np.fill_diagonal(d, np.inf)
        return d.min() / task.noise
    assert margin(easy) > margin(hard)
