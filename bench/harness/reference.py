"""The plain reference the cells are judged against.

It imports nothing of the program and takes nothing the program made: it
gets the 0/1 context the harness generated and the queries the harness
sent.  Every count is a matrix product of 0/1 values held in bfloat16 and
accumulated in float32, which is exact for counts below 2**24 (the largest
context has 103,950 objects):

  extent(Q)  = objects g with |Q ∩ row_g| = |Q|
  closure(Q) = attributes a with |extent(Q) ∩ col_a| = |extent(Q)|

The iceberg lattice is mined breadth-first: from closure(∅), close every
``intent ∪ {a}`` of the newest concepts, keep the frequent closures that
were not seen before, and stop when a level brings none.  Every frequent
closed set is reached, since each one other than closure(∅) is the
closure of ``C ∪ {a}`` for some frequent closed C just below it.

``acc=jnp.bfloat16`` accumulates the counts in bfloat16 instead: the
control, one precision step below, which the comparison has to refuse.

Sets travel packed as the program speaks them: uint32 words, attribute
``a`` at bit ``a % 32`` of word ``a // 32``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

WORD = 32
EXACT = jnp.float32
CONTROL = jnp.bfloat16


def pack(dense: np.ndarray) -> np.ndarray:
    """``[..., m]`` bool → ``[..., ceil(m/32)]`` uint32 words."""
    dense = np.asarray(dense, bool)
    m = dense.shape[-1]
    W = max(1, -(-m // WORD))
    pad = np.zeros(dense.shape[:-1] + (W * WORD - m,), bool)
    bits = np.concatenate([dense, pad], axis=-1).reshape(*dense.shape[:-1], W, WORD)
    weights = np.uint32(1) << np.arange(WORD, dtype=np.uint32)
    return (bits.astype(np.uint32) * weights).sum(axis=-1, dtype=np.uint32)


def unpack(packed: np.ndarray, m: int) -> np.ndarray:
    """``[..., W]`` uint32 words → ``[..., m]`` bool."""
    packed = np.asarray(packed, np.uint32)
    bits = (packed[..., None] >> np.arange(WORD, dtype=np.uint32)) & 1
    return bits.reshape(*packed.shape[:-1], -1)[..., :m].astype(bool)


def row_keys(packed: np.ndarray) -> list[bytes]:
    """One hashable key per packed set."""
    packed = np.ascontiguousarray(packed, np.uint32)
    return [r.tobytes() for r in packed]


@functools.partial(jax.jit, static_argnames=("acc",))
def _closure_block(D, Q, *, acc):
    """closure and support of each 0/1 row of ``Q [B, m]`` over ``D [N, m]``."""
    size = Q.astype(jnp.float32).sum(axis=1).astype(acc)
    hits = jnp.dot(Q, D.T, preferred_element_type=acc)  # [B, N]
    ext = (hits == size[:, None]).astype(jnp.bfloat16)
    support = ext.astype(acc).sum(axis=1, dtype=acc)
    common = jnp.dot(ext, D, preferred_element_type=acc)  # [B, m]
    return common == support[:, None], support.astype(jnp.float32).astype(jnp.int32)


@jax.jit
def _ranked_contains(closures, intents, scores):
    """``[B, C]`` int32: a concept's score where its intent contains the
    row's closure, else -1 (|closure ∩ intent| = |closure|)."""
    size = closures.astype(jnp.float32).sum(axis=1)
    inter = jnp.dot(closures, intents.T, preferred_element_type=jnp.float32)
    return jnp.where(inter == size[:, None], scores[None, :], -1)


class Reference:
    """Closures, the iceberg lattice and a concept store's answers over
    one context ``dense [N, m]`` (bool), on JAX's default device."""

    def __init__(self, dense: np.ndarray, *, acc=EXACT, block: int = 1024):
        dense = np.asarray(dense, bool)
        self.n_objects, self.n_attrs = dense.shape
        self.D = jnp.asarray(dense, jnp.bfloat16)
        self.acc = acc
        self.block = block

    def closures(self, sets: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """``sets [B, m]`` bool → (closures [B, m] bool, supports [B])."""
        sets = np.asarray(sets, bool)
        B = sets.shape[0]
        out_c = np.empty((B, self.n_attrs), bool)
        out_s = np.empty((B,), np.int64)
        for lo in range(0, B, self.block):
            chunk = sets[lo : lo + self.block]
            n = chunk.shape[0]
            if n < self.block:  # one compiled shape
                chunk = np.concatenate(
                    [chunk, np.zeros((self.block - n, self.n_attrs), bool)]
                )
            c, s = _closure_block(
                self.D, jnp.asarray(chunk, jnp.bfloat16), acc=self.acc
            )
            out_c[lo : lo + n] = np.asarray(c)[:n]
            out_s[lo : lo + n] = np.asarray(s)[:n]
        return out_c, out_s

    def iceberg(self, min_support: int) -> tuple[np.ndarray, np.ndarray]:
        """Every closed intent with support ≥ ``min_support`` →
        (intents [C, m] bool, supports [C]), in discovery order."""
        root, s0 = self.closures(np.zeros((1, self.n_attrs), bool))
        if s0[0] < min_support:
            return np.zeros((0, self.n_attrs), bool), np.zeros((0,), np.int64)
        intents, supports = [root], [s0]
        seen = set(row_keys(pack(root)))
        frontier = root
        while frontier.shape[0]:
            parent, attr = np.nonzero(~frontier)
            cands = frontier[parent]
            cands[np.arange(attr.size), attr] = True
            closed, sup = self.closures(cands)
            keep = sup >= min_support
            closed, sup = closed[keep], sup[keep]
            new = []
            for i, key in enumerate(row_keys(pack(closed))):
                if key not in seen:
                    seen.add(key)
                    new.append(i)
            frontier = closed[new]
            intents.append(frontier)
            supports.append(sup[new])
        return np.concatenate(intents), np.concatenate(supports)


# ---------------------------------------------------------------------------
# the concept store's answers
# ---------------------------------------------------------------------------


def store_order(intents: np.ndarray) -> np.ndarray:
    """The permutation that puts a store's intents ([C, m] bool) in the
    order its concept ids count: ascending ``(head + 1)·(m + 2) + |intent|``
    (head: the smallest attribute, -1 for the empty set), then the packed
    words, first word first."""
    C, m = intents.shape
    head = np.where(intents.any(axis=1), intents.argmax(axis=1), -1)
    key = (head + 1) * (m + 2) + intents.sum(axis=1)
    words = pack(intents)
    return np.lexsort(tuple(words[:, w] for w in reversed(range(words.shape[1]))) + (key,))


class StoreAnswers:
    """What a concept store over ``ref``'s iceberg lattice answers: closure
    (intent, support, id), top-k (ids, supports by support, then id) and
    lookup (id, -1 for a set that is no concept of the store)."""

    def __init__(self, ref: Reference, min_support: int):
        self.ref = ref
        intents, supports = ref.iceberg(min_support)
        order = store_order(intents)
        self.intents, self.supports = intents[order], supports[order]
        self.ids = {k: i for i, k in enumerate(row_keys(pack(self.intents)))}

    def lookup(self, sets: np.ndarray) -> np.ndarray:
        return np.array([self.ids.get(k, -1) for k in row_keys(pack(sets))], np.int64)

    def closure(self, queries: np.ndarray):
        closed, sup = self.ref.closures(queries)
        return closed, sup, self.lookup(closed)

    def topk(self, queries: np.ndarray, k: int):
        closed, _ = self.ref.closures(queries)
        C = self.intents.shape[0]
        # one int32 score orders by support, then by ascending id
        scores = jnp.asarray(self.supports * C + (C - 1 - np.arange(C)), jnp.int32)
        intents = jnp.asarray(self.intents, jnp.bfloat16)
        ids = np.empty((closed.shape[0], k), np.int64)
        sups = np.empty((closed.shape[0], k), np.int64)
        step = self.ref.block
        for lo in range(0, closed.shape[0], step):
            chunk = closed[lo : lo + step]
            n = chunk.shape[0]
            chunk = np.concatenate([chunk, np.zeros((step - n, chunk.shape[1]), bool)])
            ranked = _ranked_contains(jnp.asarray(chunk, jnp.bfloat16), intents, scores)
            top = np.asarray(jax.lax.top_k(ranked, min(k, C))[0])[:n].astype(np.int64)
            if top.shape[1] < k:
                top = np.pad(top, ((0, 0), (0, k - top.shape[1])), constant_values=-1)
            hit = top >= 0
            ids[lo : lo + step] = np.where(hit, C - 1 - top % C, -1)
            sups[lo : lo + step] = np.where(hit, top // C, -1)
        return ids, sups
