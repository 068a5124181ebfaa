"""merge_topk's rank-then-sort formulation against the lexicographic-sort
form it replaced: same canonical order (key descending, state words ascending
as signed int32), same dedup of identical (state, key) pairs, same zeroed
empty slots."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.api import NEG
from repro.core.engine import merge_topk


def lexsort_merge_topk(states, keys, k):
    """The former implementation (in numpy, which compiles nothing): one
    lexsort operand per state word, key least significant, then a stable
    descending sort on the deduplicated keys."""
    states, keys = np.asarray(states), np.asarray(keys)
    s = states.shape[-1]
    lex = np.lexsort((keys,) + tuple(states[:, j]
                                     for j in reversed(range(s))))
    ss, kk = states[lex], keys[lex]
    dup = np.concatenate([
        np.zeros((1,), bool),
        np.all(ss[1:] == ss[:-1], axis=1) & (kk[1:] == kk[:-1])])
    kk = np.where(dup, NEG, kk)
    top = np.argsort(-kk.astype(np.int64), kind="stable")[:k]
    top_keys = kk[top]
    top_states = np.where((top_keys > NEG)[:, None], ss[top], 0)
    return top_states, top_keys


def _candidates(rng, n, s, n_keys, dup_frac, neg_frac):
    """Random wide states drawn from a small pool (so prefixes, whole
    rows and keys collide), full-range signed words, duplicated rows and
    NEG-keyed rows, including NEG-keyed copies of live states."""
    pool = rng.integers(-2 ** 31, 2 ** 31, (max(2, n // 3), s),
                        dtype=np.int64).astype(np.int32)
    # shared prefixes: rows that first differ deep inside the state
    pool[1::2, : s - 1] = pool[0, : s - 1]
    states = pool[rng.integers(0, len(pool), n)]
    keys = rng.integers(0, n_keys, n).astype(np.int32)
    dups = rng.random(n) < dup_frac
    src = rng.integers(0, n, n)
    states[dups] = states[src[dups]]
    keys[dups] = keys[src[dups]]
    keys[rng.random(n) < neg_frac] = NEG
    return jnp.asarray(states), jnp.asarray(keys)


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("n,s,k", [(67, 2050, 3), (19, 514, 5),
                                   (12, 7, 12), (40, 1, 8),
                                   (1064, 2050, 1000)])
def test_merge_topk_matches_lexsort_oracle(seed, n, s, k):
    rng = np.random.default_rng(seed * 1000 + n + s)
    states, keys = _candidates(rng, n, s, n_keys=4, dup_frac=0.3,
                               neg_frac=0.2)
    got = jax.jit(merge_topk, static_argnums=2)(states, keys, k)
    want = lexsort_merge_topk(states, keys, k)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))


def test_merge_topk_all_empty_and_all_duplicate():
    s = 9
    states = jnp.asarray(np.arange(5 * s, dtype=np.int32).reshape(5, s))
    empty = jnp.full((5,), NEG, jnp.int32)
    got_s, got_k = merge_topk(states, empty, 3)
    assert (np.asarray(got_k) == NEG).all()
    assert (np.asarray(got_s) == 0).all()
    same = jnp.broadcast_to(states[2], (5, s))
    got_s, got_k = merge_topk(same, jnp.full((5,), 7, jnp.int32), 3)
    assert np.asarray(got_k).tolist() == [7, NEG, NEG]
    np.testing.assert_array_equal(np.asarray(got_s[0]), np.asarray(states[2]))
