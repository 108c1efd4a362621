"""The benchmark's traffic: the serving mix is fixed by its file and the
seed, every request in a window is a distinct revision inside the node
range, and every seed gets the one schedule: the same requests at the
same times in the same order."""
import collections
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from chipbench import traffic  # noqa: E402

with open(os.path.join(ROOT, "chipbench", "traffic", "serve-miss.json")) as f:
    MIX = json.load(f)


def test_family_counts_follow_zipf_and_sum():
    counts = traffic.family_counts(100, 5, 1.0)
    assert sum(counts) == 100
    assert counts == sorted(counts, reverse=True)
    assert counts[0] == 44            # 100 / (1 + 1/2 + 1/3 + 1/4 + 1/5)


@pytest.mark.parametrize("seconds", [10.0, 51.0])
def test_same_seed_same_requests(seconds):
    a = traffic.window_requests(MIX, seconds)
    b = traffic.window_requests(MIX, seconds)
    assert a == b


@pytest.mark.parametrize("seconds", [10.0, 51.0])
def test_requests_are_distinct_revisions_in_range(seconds):
    reqs = traffic.window_requests(MIX, seconds)
    assert len(reqs) == round(MIX["rate_rps"] * seconds)
    keys = [(fam, json.dumps(kw, sort_keys=True)) for _, fam, kw in reqs]
    assert len(set(keys)) == len(keys)
    nodes = {(f["family"], json.dumps(r["kwargs"], sort_keys=True)):
             r["nodes"] for f in MIX["families"] for r in f["revisions"]}
    lo, hi = MIX["node_range"]
    assert all(lo <= nodes[k] <= hi for k in keys)
    due = [t for t, _, _ in reqs]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < seconds


def test_the_schedule_mixes_families_and_sizes_at_the_rate():
    a = traffic.window_requests(MIX, 51.0)
    rate = len(a) / 51.0
    assert abs(rate - MIX["rate_rps"]) < 0.05
    fams = [f for _, f, _ in a]
    counts = collections.Counter(fams)
    assert [counts[f["family"]] for f in MIX["families"]] == \
        traffic.family_counts(len(a), len(MIX["families"]), MIX["zipf_skew"])
    # the fixed order interleaves the families: no family arrives as one
    # run, and each half of the window gets some of each
    half = len(a) // 2
    for f in counts:
        assert 0 < fams[:half].count(f) < counts[f] or counts[f] == 1


def test_listed_node_counts_match_the_generators():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.graphs import synthetic
    rng = np.random.default_rng(0)
    for fam in MIX["families"]:
        for i in rng.choice(len(fam["revisions"]), 3, replace=False):
            r = fam["revisions"][i]
            g = getattr(synthetic, fam["family"])(**r["kwargs"])
            assert g.num_nodes == r["nodes"]


def test_warm_up_covers_each_bucket_once_and_is_not_in_the_window():
    buckets = sorted({min(b for b in (256, 512, 1024, 2048, 4096)
                          if b >= w["nodes"]) for w in MIX["warmup"]})
    assert buckets == [256, 512, 1024, 2048, 4096]
    listed = {(f["family"], json.dumps(r["kwargs"], sort_keys=True))
              for f in MIX["families"] for r in f["revisions"]}
    for w in MIX["warmup"]:
        assert (w["family"], json.dumps(w["kwargs"], sort_keys=True)) \
            not in listed
