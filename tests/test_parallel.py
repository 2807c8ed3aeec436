"""Multi-chip sharding tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest


def test_graft_entry_single():
    import jax

    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    batch = args[3].shape[0]  # bytes_mat
    assert int(out["stats"]["routed"]) == batch
    assert not bool(np.asarray(out["flags"]).any())


@pytest.mark.parametrize("n", [2, 4, 8])
def test_dryrun_multichip(n):
    import __graft_entry__ as ge

    ge.dryrun_multichip(n)


def _local_and_dist(force_residual, topics):
    """-> (the local serving step's outputs, the 8-device mesh step's:
    dp 4 x tp 2, so 16 rows a batch shard) over 96 filters of four
    shapes; with `force_residual` the index takes two shapes and the
    other two are the residual NFA engine's."""
    import __graft_entry__ as ge
    from emqx_tpu.models.router_model import SubscriberTable, shape_route_step
    from emqx_tpu.ops.route_index import RouteIndex
    from emqx_tpu.ops.tokenizer import encode_topics
    from emqx_tpu.parallel.mesh import (
        dist_shape_route_step,
        make_mesh,
        shard_shape_inputs,
    )

    index = RouteIndex(max_shapes=2 if force_residual else 64)
    subs = SubscriberTable(max_subscribers=512)
    shapes = ["device/%d/+/t%d/#", "plant/%d/s%d", "+/%d/x/%d", "q/%d/%d/#"]
    for i in range(96):
        fid = index.add(shapes[i % 4] % (i % 16, i))
        subs.add(fid, i % 512)
    assert (index.residual_count > 0) == force_residual
    with_nfa = index.residual_count > 0
    bytes_mat, lengths, _ = encode_topics(topics, 64)
    sub_bitmaps = subs.pack(index.num_filters_capacity)
    m_active = index.shapes.m_active()

    st = index.shapes.device_snapshot()
    nt = index.nfa.device_snapshot() if with_nfa else None
    local = shape_route_step(
        {k: v.copy() for k, v in st.items()},
        {k: v.copy() for k, v in nt.items()} if nt is not None else None,
        sub_bitmaps,
        bytes_mat,
        np.asarray(lengths),
        m_active=m_active, with_nfa=with_nfa, salt=index.salt, **ge._CFG,
    )
    mesh = make_mesh(8)
    dst, dnt, sb, bm, ln = shard_shape_inputs(
        mesh, st, nt, sub_bitmaps, bytes_mat, np.asarray(lengths)
    )
    dist = dist_shape_route_step(
        mesh, dst, dnt, sb, bm, ln,
        m_active=m_active, salt=index.salt, **ge._CFG,
    )
    for k in ("matched", "flags", "bitmaps"):
        np.testing.assert_array_equal(
            np.asarray(local[k]), np.asarray(dist[k]), err_msg=k
        )
    for k in local["stats"]:
        assert int(local["stats"][k]) == int(dist["stats"][k]), k
    return local, dist


@pytest.mark.parametrize("force_residual", [False, True])
def test_dist_matches_single_device(force_residual):
    """The sharded serving step must equal the local step bit-for-bit —
    both with an empty residual engine and with live NFA lanes (forced
    via a tiny max_shapes so some filters overflow into the NFA)."""
    local, dist = _local_and_dist(
        force_residual, [f"device/{i % 16}/x/t{i}/y" for i in range(64)]
    )
    assert ("nfa_flagged" in dist) == force_residual
    if force_residual:
        np.testing.assert_array_equal(
            np.asarray(local["nfa_flagged"]), np.asarray(dist["nfa_flagged"])
        )


def test_dist_walks_each_shard_to_its_own_deepest_row():
    """The batch shards' deepest rows differ: 8 levels (the budget of
    `_CFG`) in the first, one row of 10 (too deep) in the second, 4 in
    the last two. Every row equals the local step's, and so do the
    residual engine's six ints: the flagged counts summed over the
    shards, the levels walked and the widest frontier their max."""
    topics = [f"q/{j % 16}/{j}/a/b/c/d/e" for j in range(3, 64, 4)]
    topics += ["q/3/3/a/b/c/d/e/f/g"]
    topics += [f"q/{j % 16}/{j}/z" for j in range(7, 64, 4)][:15]
    topics += [f"w{j}/{j % 16}/x/{j}" for j in range(2, 128, 4)]
    assert len(topics) == 64
    local, dist = _local_and_dist(True, topics)
    flagged = np.asarray(dist["nfa_flagged"])
    np.testing.assert_array_equal(np.asarray(local["nfa_flagged"]), flagged)
    # one row flagged, too deep; 8 levels walked; two live states at
    # most: a row's literal path and the `+` of `+/%d/x/%d` at the root
    assert flagged.tolist() == [1, 1, 0, 0, 8, 2]
    assert np.asarray(local["flags"]).tolist() == [i == 16 for i in range(64)]
