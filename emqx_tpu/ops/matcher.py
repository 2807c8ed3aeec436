"""Batched NFA topic matching on TPU.

Replaces the per-message ETS trie walk of the reference
(apps/emqx/src/emqx_trie.erl:271-333 `match_no_compact`, driven from
emqx_router:match_routes emqx_router.erl:128-141) with one jitted SPMD kernel
over a *batch* of topics:

- state: a fixed-width frontier of NFA node ids per topic (a trie has no
  converging paths, so the frontier never contains duplicates);
- one `lax.scan` step per topic level: gather `#`-terminals (they match any
  non-empty suffix), probe the literal-edge hash table, gather `+` children,
  then compact the doubled frontier with a cumsum+scatter;
- end-of-scan: collect exact terminals and `#`-terminals of the surviving
  frontier (``a/#`` matches ``a`` — 'match_#' at emqx_trie.erl:288-291);
- `$`-topics skip root-level ``+``/``#`` (emqx_trie.erl:271-278).

Everything is static-shape, data-independent control flow; matched filter ids
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
    max_levels: int = 16  # topic depth budget (scan length)
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
    """Left-pack the >=0 entries of cand [B, W] into [B, width]; flag overflow."""
    import jax.numpy as jnp

    B = cand.shape[0]
    valid = cand >= 0
    pos = jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
    idx = jnp.where(valid & (pos < width), pos, width)
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    out = jnp.full((B, width), -1, dtype=jnp.int32)
    out = out.at[rows, idx].set(cand, mode="drop")
    over = jnp.sum(valid, axis=1) > width
    return out, over


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
       the fast path missed, not just that it did)
    """
    import jax
    import jax.numpy as jnp

    B, L = syms.shape
    F, K = frontier, max_matches

    # derive carry inits from the inputs so they carry the same device-varying
    # type as the loop body under shard_map (see shard_map scan-vma docs)
    z = jnp.zeros_like(nwords)  # [B] int32
    frontier0 = jnp.full((B, F), -1, dtype=jnp.int32) + z[:, None]
    frontier0 = frontier0.at[:, 0].set(z)  # root
    matched0 = jnp.full((B, K), -1, dtype=jnp.int32) + z[:, None]
    mcount0 = z
    fover0 = z < 0  # all-False, device-varying

    def step(carry, xs):
        fr, matched, mcount, fover = carry
        wsym, lvl = xs
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
        newf, over = _compact(jnp.concatenate([lit, plus], axis=1), F)
        fr = jnp.where(active_row[:, None], newf, fr)
        fover = fover | (over & active_row)
        return (fr, matched, mcount, fover), None

    (fr, matched, mcount, fover), _ = jax.lax.scan(
        step,
        (frontier0, matched0, mcount0, fover0),
        (syms.T, jnp.arange(L, dtype=jnp.int32)),
    )

    done = nwords <= L
    fin = (fr >= 0) & done[:, None]
    fr_safe = jnp.maximum(fr, 0)
    term = jnp.where(fin, tables["term_filter"][fr_safe], -1)
    matched, mcount = _append(matched, mcount, term, K)
    endhash = jnp.where(fin, tables["hash_filter"][fr_safe], -1)
    matched, mcount = _append(matched, mcount, endhash, K)

    too_deep = ~done
    mover = mcount > K
    flags = fover | mover | too_deep
    causes = {
        "too_deep": too_deep,
        "frontier_overflow": fover,
        "match_overflow": mover,
    }
    return matched, jnp.minimum(mcount, K), flags, causes


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
