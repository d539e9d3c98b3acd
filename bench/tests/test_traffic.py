import os

import numpy as np

from bench import data, drive
from bench.run import ROOT, load_json
from bench.tests.helpers import SPEC


def _cell(seed):
    conf = {c["name"]: c["file"] for c in SPEC["configs"]}["sift128-l2"]
    config = load_json(os.path.join(ROOT, conf))
    return drive.Cell(name="sift128-l2.batch", config=config, mix={}, seed=seed, seconds=1.0)


def test_every_seed_gets_the_same_catalog():
    a = np.asarray(drive.make_rows(_cell(1), 256))
    b = np.asarray(drive.make_rows(_cell(2**31 + 12345), 256))
    assert np.array_equal(a, b)


def test_data_is_a_function_of_the_seed_also_past_32_bits():
    cfg = {"generator": "clustered", "params": {"n_clusters": 8}}
    big = 2**31 + 12345
    a = np.asarray(data.make(cfg, data.seed_key(big), 64, 16))
    b = np.asarray(data.make(cfg, data.seed_key(big), 64, 16))
    c = np.asarray(data.make(cfg, data.seed_key(12345), 64, 16))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
