"""Input kinds found by name (``bench/inputs/<kind>.py``): the batches a
seed gives, pinned by a checksum of the first batch of each tiny cell's
data, so that a move or an edit of a kind that changes one bit shows."""
import hashlib

import numpy as np
import pytest

from bench.lib import data as data_mod
from bench.lib import harness as H
from bench.tests import tiny

FIRST_BATCH = {
    ("smollm-kfac-train", 5): "d2efa2c3df342359",
    ("smollm-kfac-train", 2_147_483_711): "0f63f86a29504414",
    ("ae-kfac-train", 5): "6dcffcdb19fd07e1",
    ("ae-kfac-train", 2_147_483_711): "6b04d551fdfd34a9",
}


def digest(batch: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(batch):
        h.update(k.encode())
        h.update(np.asarray(batch[k]).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name,seed", sorted(FIRST_BATCH))
def test_first_batch_is_pinned(name, seed):
    c = tiny.cell(name)
    spec = c["traffic"]["data"]
    data = data_mod.make(spec, c["config"], H.seed_key(seed))
    assert digest(data.batch(0)) == FIRST_BATCH[name, seed]
    again = data_mod.make(spec, c["config"], H.seed_key(seed))
    n = len(data.batches)
    assert digest(again.batch(n)) == digest(data.batch(0))   # cycled


def test_unknown_kind_names_its_file():
    with pytest.raises(FileNotFoundError, match=r"inputs/no_such_kind\.py"):
        data_mod.make({"kind": "no_such_kind"}, {}, H.seed_key(0))
