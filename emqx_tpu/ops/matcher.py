"""Batched NFA topic matching on TPU.

Replaces the per-message ETS trie walk of the reference
(apps/emqx/src/emqx_trie.erl:271-333 `match_no_compact`, driven from
emqx_router:match_routes emqx_router.erl:128-141) with one jitted SPMD kernel
over a *batch* of topics:

- state: a fixed-width frontier of NFA node ids per topic (a trie has no
  converging paths, so the frontier never contains duplicates);
- one `lax.while_loop` trip per topic level, up to the batch's deepest row
  (at most the level budget): gather `#`-terminals (they match any
  non-empty suffix), probe the literal-edge hash table, gather `+` children,
  then compact the doubled frontier with a cumsum+scatter;
- end of the walk: collect exact terminals and `#`-terminals of the surviving
  frontier (``a/#`` matches ``a`` — 'match_#' at emqx_trie.erl:288-291);
- `$`-topics skip root-level ``+``/``#`` (emqx_trie.erl:271-278).

Everything is static-shape; the one data-dependent bound is the trip count,
a device scalar, so one program serves every depth. Matched filter ids
accumulate into a fixed [B, K] buffer via cumsum+scatter with an overflow
flag. Rows that overflow (frontier or matches) or exceed the level budget are
flagged so the host can fall back to the authoritative CPU trie
(`emqx_tpu.broker.trie.TopicTrie`) — correctness never depends on the caps.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax

from emqx_tpu.ops.nfa import (
    EDGE_H_MUL_NODE,
    EDGE_H_MUL_SYM,
    EDGE_H_SHIFT,
    MAX_PROBES,
)


@dataclass(frozen=True)
class MatcherConfig:
    max_levels: int = 16  # topic depth budget (the walk's longest)
    frontier: int = 32  # max simultaneous NFA states per topic
    max_matches: int = 64  # max matched filters per topic
    # open-addressing probe bound; must cover the build-time bound
    # (nfa.MAX_PROBES) or lookups would silently miss — DeviceRouter clamps.
    probes: int = MAX_PROBES
    max_bytes: int = 256  # topic byte budget for the device tokenizer
    # sparse fan-out compaction (router_model.compact_fanout_slots):
    # read back O(matches) slot lists instead of dense [B, W] bitmaps;
    # overflow rows fall back to a masked dense transfer, so the cap is
    # a bandwidth knob, never a correctness one
    fanout_compact: bool = True
    # per-row compact-slot cap: 0 = auto-size from the dispatch.fanout
    # histogram p99 (grow-only, pow2-padded); > 0 pins it (pow2-padded)
    fanout_slots: int = 0
    # subscriber-table representation policy (router.sub_table,
    # docs/serving_pipeline.md "subscriber-table memory budget"):
    # "dense" pins the [Fcap, W] bitmap matrix (the degrade fallback),
    # "sparse" pins the CSR slot lists (O(total subscriptions) memory),
    # "auto" starts dense and flips ONCE when occupancy x width says
    # the matrix is mostly zeros
    sub_table: str = "auto"
    # CSR gather-window bound per row (sparse mode): rows whose matched
    # regions exceed it rebuild on host like Kslot overflow. 0 = auto
    # (2 x Kslot, tracking the fanout p99)
    sparse_gather: int = 0
    # donate the per-batch input buffers (token bytes, lengths) to the
    # serving-path jit so steady-state batches reuse them for outputs
    # instead of allocating fresh device buffers every launch
    donate_buffers: bool = True
    # bound on cached compiled programs per serving-path jit entry: table
    # growth / config transitions each compile a fresh program, and a
    # long-lived process must not accumulate every shape it ever served.
    # 0 disables trimming.
    jit_cache_max: int = 64


def _probe_edges(tables, node, sym, probes: int):
    """Vectorized open-addressing lookup of literal edges (node, sym)->child."""
    import jax.numpy as jnp

    E = tables["edge_node"].shape[0]
    mask = jnp.uint32(E - 1)
    valid = (node >= 0) & (sym >= 0)
    h = node.astype(jnp.uint32) * jnp.uint32(EDGE_H_MUL_NODE) + sym.astype(
        jnp.uint32
    ) * jnp.uint32(EDGE_H_MUL_SYM)
    h ^= h >> EDGE_H_SHIFT
    child = jnp.full(node.shape, -1, dtype=jnp.int32)
    found = jnp.zeros(node.shape, dtype=bool)
    for p in range(probes):
        idx = ((h + jnp.uint32(p)) & mask).astype(jnp.int32)
        hit = (
            (tables["edge_node"][idx] == node)
            & (tables["edge_sym"][idx] == sym)
            & valid
            & ~found
        )
        child = jnp.where(hit, tables["edge_child"][idx], child)
        found |= hit
    return child


def _compact(cand, width: int):
    """Left-pack the >=0 entries of cand [B, W] into [B, width].

    -> (packed [B, width], count [B] of the >=0 entries); a row whose
    count is over `width` lost its tail."""
    import jax.numpy as jnp

    B = cand.shape[0]
    valid = cand >= 0
    pos = jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
    idx = jnp.where(valid & (pos < width), pos, width)
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    out = jnp.full((B, width), -1, dtype=jnp.int32)
    out = out.at[rows, idx].set(cand, mode="drop")
    return out, jnp.sum(valid, axis=1, dtype=jnp.int32)


def _member_mask(matched, ids):
    """bool [B, N]: ids[n] appears in row b's matched set [B, K].

    lax.scan over the K matched columns keeps peak memory at one [B, N]
    mask instead of materializing [B, K, N]. The initial carry is derived
    from both operands so that under shard_map it varies over the same
    mesh axes as the body's output (scan requires equal carry types).
    """
    import jax
    import jax.numpy as jnp

    def _memb(acc, mcol):  # mcol: [B] one matched column
        return acc | (mcol[:, None] == ids[None, :]), None

    memb0 = (matched[:, :1] == ids[None, :]) & False
    memb, _ = jax.lax.scan(_memb, memb0, jnp.swapaxes(matched, 0, 1))
    return memb


def _append(matched, mcount, hits, cap: int):
    """Append the >=0 entries of hits [B, H] to matched [B, cap] at mcount."""
    import jax.numpy as jnp

    B = matched.shape[0]
    valid = hits >= 0
    pos = mcount[:, None] + jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
    idx = jnp.where(valid & (pos < cap), pos, cap)
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    matched = matched.at[rows, idx].set(hits, mode="drop")
    return matched, mcount + jnp.sum(valid, axis=1, dtype=jnp.int32)


@partial(jax.jit, static_argnames=("frontier", "max_matches", "probes"))
def batch_match_syms(
    tables,
    syms,
    nwords,
    dollar,
    *,
    frontier: int = 32,
    max_matches: int = 64,
    probes: int = 8,
):
    """Match pre-tokenized topics against the NFA tables.

    syms: int32 [B, L] dense word symbols (-1 = OOV/absent)
    nwords: int32 [B]; dollar: bool [B]
    -> matched int32 [B, K] filter ids (-1 padded), mcount int32 [B],
       flags bool [B] (overflow or too-deep => host must fall back),
       causes {too_deep, frontier_overflow, match_overflow} bool [B]
       (per-cause breakdown of flags — the flight recorder counts WHY
       the fast path missed, not just that it did),
       walk {levels, frontier_peak} int32 scalars: the levels walked and
       the most live NFA states (before the `frontier` cap) any row had
       after a walked level

    The walk stops at the batch's deepest row: min(L, max(nwords))
    trips, a device scalar, so there is one program whatever the depth.
    L stays the budget: a deeper row is flagged too_deep. A trip past a
    row's last level changes nothing in that row, so the outputs are
    those of a walk over all L levels.
    """
    import jax
    import jax.numpy as jnp

    B, L = syms.shape
    F, K = frontier, max_matches

    # derive carry inits from the inputs so they carry the same device-varying
    # type as the loop body under shard_map (see shard_map scan-vma docs);
    # the trip bound too: each shard walks to its own deepest row
    z = jnp.zeros_like(nwords)  # [B] int32
    frontier0 = jnp.full((B, F), -1, dtype=jnp.int32) + z[:, None]
    frontier0 = frontier0.at[:, 0].set(z)  # root
    matched0 = jnp.full((B, K), -1, dtype=jnp.int32) + z[:, None]
    levels = jnp.minimum(jnp.max(nwords, initial=0), L)
    # a level's symbols as a row: indexing the major axis per trip is
    # cheaper on the chip than a column of syms (PERF.md section 6, PR 38)
    syms_by_level = syms.T

    def walked(carry):
        return carry[0] < levels

    def step(carry):
        lvl, fr, matched, mcount, widest = carry
        wsym = syms_by_level[lvl]
        active_row = lvl < nwords
        act = (fr >= 0) & active_row[:, None]
        fr_safe = jnp.maximum(fr, 0)
        allow_wild = act & ~((lvl == 0) & dollar)[:, None]
        # '#' children match any non-empty remaining suffix
        hf = jnp.where(allow_wild, tables["hash_filter"][fr_safe], -1)
        matched, mcount = _append(matched, mcount, hf, K)
        lit = _probe_edges(
            tables,
            jnp.where(act, fr, -1),
            jnp.broadcast_to(wsym[:, None], (B, F)),
            probes,
        )
        plus = jnp.where(allow_wild, tables["plus_child"][fr_safe], -1)
        newf, live = _compact(jnp.concatenate([lit, plus], axis=1), F)
        fr = jnp.where(active_row[:, None], newf, fr)
        # a row's most live states after any of its levels: over F, its
        # frontier lost states (frontier_overflow)
        widest = jnp.maximum(widest, jnp.where(active_row, live, 0))
        return lvl + 1, fr, matched, mcount, widest

    _, fr, matched, mcount, widest = jax.lax.while_loop(
        walked, step, (jnp.int32(0), frontier0, matched0, z, z)
    )

    done = nwords <= L
    fin = (fr >= 0) & done[:, None]
    fr_safe = jnp.maximum(fr, 0)
    term = jnp.where(fin, tables["term_filter"][fr_safe], -1)
    matched, mcount = _append(matched, mcount, term, K)
    endhash = jnp.where(fin, tables["hash_filter"][fr_safe], -1)
    matched, mcount = _append(matched, mcount, endhash, K)

    too_deep = ~done
    fover = widest > F
    mover = mcount > K
    flags = fover | mover | too_deep
    causes = {
        "too_deep": too_deep,
        "frontier_overflow": fover,
        "match_overflow": mover,
    }
    walk = {"levels": levels, "frontier_peak": jnp.max(widest, initial=0)}
    return matched, jnp.minimum(mcount, K), flags, causes, walk


class MatchError(RuntimeError):
    """Per-row match failure marker (returned, never raised mid-batch).

    With ``fallback=None`` a device-flagged row (too deep / overflow /
    too long) used to raise AFTER the whole batch's device work was
    done — one oversized topic poisoned every other row's result. Now
    each flagged row yields a `MatchError` in its slot and the rest of
    the batch returns normally; callers either pass a fallback (the CPU
    trie) or filter/inspect the error rows themselves."""

    def __init__(self, topic: str, cause: str = "overflow"):
        super().__init__(
            f"device match overflow for topic {topic!r}; no fallback "
            "provided"
        )
        self.topic = topic
        self.cause = cause
