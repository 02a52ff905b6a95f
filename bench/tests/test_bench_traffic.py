"""Lengths and token streams from the traffic files: the same seed gives
the same tokens, and every seed gives the same lengths."""
import os
import sys

HERE = os.path.dirname(__file__)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(HERE, ".."))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

import pytest  # noqa: E402

import harness  # noqa: E402
import traffic as tr  # noqa: E402

ROOT = harness.ROOT
FILES = sorted((ROOT / "bench" / "traffic").glob("*.json"))


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_frozen_lengths_are_the_files_sample(path):
    t = harness.load_json(path)
    for ten in t["tenants"]:
        prof = t["length_profiles"][ten["lengths"]]
        want = tr.frozen_lengths(prof, len(ten["frozen_lengths"]), ten["length_seed"])
        assert ten["frozen_lengths"] == want
        assert max(want) <= prof["pad"]


def test_normal_profiles_are_the_programs():
    from repro.data.synthetic import DATASETS

    for name, (pad, mean, std) in tr.NORMAL_PROFILES.items():
        ds = DATASETS[name]
        assert (ds.pad_len, ds.mean_frac, ds.std_frac) == (pad, mean, std), name


def test_token_stream_is_the_programs():
    import itertools

    from repro.data.synthetic import token_stream

    mine = list(itertools.islice(tr.token_stream("t01", 49152, 2**31 + 3), 200))
    theirs = list(itertools.islice(token_stream("t01", 49152, 2**31 + 3), 200))
    assert mine == theirs


def test_token_stream_repeats_for_a_seed_and_differs_across_seeds():
    import itertools

    a = list(itertools.islice(tr.token_stream("s01", 49152, 2**31 + 9), 64))
    b = list(itertools.islice(tr.token_stream("s01", 49152, 2**31 + 9), 64))
    c = list(itertools.islice(tr.token_stream("s01", 49152, 2**31 + 10), 64))
    assert a == b and a != c
