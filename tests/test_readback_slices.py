"""`DeviceRouter._pull`: a batch's outputs cross the link sliced to the
live rows rounded up to an eighth of the launch's bucket and are trimmed on
the host. Whatever sizes a bucket's batches have, it meets at most eight
slice programs per output (each static length is a program compiled at its
first use), and the host sees exactly the live rows."""

import types

import numpy as np
import pytest

from emqx_tpu.models.router_model import DeviceRouter


class Recording:
    """A device array's stand-in: remembers the slices taken of it."""

    def __init__(self, arr, seen):
        self.arr, self.seen, self.shape = arr, seen, arr.shape

    def __getitem__(self, key):
        self.seen.add(key if isinstance(key, slice) else key[-1])
        return self.arr[key]


@pytest.mark.parametrize("cap", [64, 256, 4096])
def test_a_bucket_meets_at_most_eight_slice_lengths_and_the_host_sees_b_rows(cap):
    rng = np.random.default_rng(cap)
    arrays = {"matched": rng.integers(0, 9, (cap, 4)),
              "mcount": rng.integers(0, 4, cap),
              "flags": rng.integers(0, 2, cap).astype(bool),
              "slots": rng.integers(-1, 99, (cap, 16)),
              "slot_count": rng.integers(0, 16, cap),
              "rule_masks": rng.integers(0, 2, (3, cap)).astype(bool)}
    seen = set()
    out = {k: Recording(v, seen) for k, v in arrays.items()}
    out["bitmaps"] = None
    me = types.SimpleNamespace(PULL_STEPS=DeviceRouter.PULL_STEPS)
    for B in range(1, cap + 1):
        host = DeviceRouter._pull(me, out, B, False, 16, False, None, None, None)
        for k, v in arrays.items():
            want = v[:, :B] if k == "rule_masks" else v[:B]
            assert host[k].shape == want.shape and (host[k] == want).all(), (k, B)
    lengths = {s.stop for s in seen}
    assert len(lengths) <= DeviceRouter.PULL_STEPS and max(lengths) == cap
    assert all(n % max(1, cap // DeviceRouter.PULL_STEPS) == 0 for n in lengths)
