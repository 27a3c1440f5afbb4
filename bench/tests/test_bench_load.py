"""The load generator (bench/load.py): open-loop schedules give every
seed the same work in another order, and the row distributions stay
in range."""
import numpy as np
import pytest

import load

MIX = {"streams": [{"arrival": "poisson", "rate_per_s": 500,
                    "rows": {"dist": "zipf", "s": 1.5, "min": 1, "max": 32}}],
       "pool_images": 256, "shape_seed": 0}


def test_seeds_share_sizes_and_gaps():
    st = MIX["streams"][0]
    a = load.schedule(st, MIX, 1, 10.0, 256)
    b = load.schedule(st, MIX, 2**31 + 17, 10.0, 256)
    assert len(a["due"]) == len(b["due"]) > 4000
    assert sorted(a["rows"]) == sorted(b["rows"])
    # the same gaps in another order: the schedules end within a gap
    assert abs(a["due"][-1] - b["due"][-1]) < 0.05
    assert not np.array_equal(a["rows"], b["rows"])
    assert a["rows"].min() >= 1 and a["rows"].max() <= 32
    assert np.all(a["off"] + a["rows"] <= 256) and a["off"].min() >= 0
    assert np.all(np.diff(a["due"]) >= 0) and a["due"][-1] < 10.0
    c = load.schedule(st, MIX, 1, 10.0, 256)
    assert all(np.array_equal(a[k], c[k]) for k in a)


def test_zipf_mean():
    st = MIX["streams"][0]
    a = load.schedule(st, MIX, 5, 20.0, 256)
    # E[k] for P(k) ~ k**-1.5 on 1..32 is 4.43
    assert a["rows"].mean() == pytest.approx(4.43, rel=0.1)


def test_warm_rows():
    import run

    bulk = {"streams": [{"arrival": "closed", "clients": 4, "rows": {"dist": "fixed", "n": 128}}]}
    assert run.warm_rows(bulk, 128) == [128]
    assert run.warm_rows(MIX, 32) == list(range(1, 33))


def test_reader_name_splits_on_the_first_dot():
    import run

    assert run.reader_name("images_per_s.imagenet") == "images_per_s"
    assert run.reader_name("fused_mlp_roofline.imagenet") == "fused_mlp_roofline"
    assert run.reader_name("setup_s") == "setup_s"
