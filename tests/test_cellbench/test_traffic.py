"""The traffic generators: the same seed gives the same inputs, another
seed gives other tokens, and neither the work nor the program (the mask)
depends on the seed."""

import glob
import json
import os

import numpy as np
import pytest

from cellbench import flops, manifest, traffic_gen

TRAFFIC = sorted(
    os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(manifest.ROOT, "cellbench", "traffic", "*.json")))


def _mask(name, seed, tokens=None, window=None):
    traffic = json.load(open(os.path.join(
        manifest.ROOT, "cellbench", "traffic", name + ".json")))
    gen = manifest.load_generator(manifest.ROOT, traffic["generator"])
    if traffic["window"] == "config":
        window = 4096
    return traffic, traffic_gen.make_mask(
        traffic, tokens or traffic["tokens"], window, seed, gen)


@pytest.mark.parametrize("name", TRAFFIC)
def test_same_seed_same_inputs_other_seed_other_inputs(name):
    traffic, a = _mask(name, 3)
    _, b = _mask(name, 3)
    _, c = _mask(name, 4)
    assert a == b
    assert a.cu_seqlens[0] == 0 and a.cu_seqlens[-1] == a.tokens
    batches = [
        traffic_gen.token_batches(s, 1000, seed, traffic["batches"])
        for s, seed in ((a, 3), (b, 3), (c, 4))]
    for (ta, la), (tb, lb) in zip(batches[0], batches[1]):
        assert np.array_equal(ta, tb) and np.array_equal(la, lb)
    assert not np.array_equal(batches[0][0][0], batches[2][0][0])
    assert not np.array_equal(batches[0][0][0], batches[0][1][0])  # the ring
    # the mask, and with it the work and the compiled step, is the same
    # whatever the seed
    assert a == c


@pytest.mark.parametrize("name", TRAFFIC)
def test_labels_are_next_tokens_with_no_target_across_documents(name):
    _, spec = _mask(name, 0)
    toks, labels = traffic_gen.token_batches(spec, 1000, 0, 1)[0]
    ends = np.asarray(spec.cu_seqlens[1:]) - 1
    assert (labels[ends] == -1).all()
    keep = np.ones(spec.tokens, bool)
    keep[ends] = False
    assert np.array_equal(labels[keep], toks[1:][keep[:-1]])


def test_packed_lengths_follow_the_law_and_order_seed_orders_them():
    traffic, a = _mask("packed", 0)
    lens = a.doc_lengths()
    other = traffic_gen.make_mask(
        {**traffic, "params": {**traffic["params"], "order_seed": 1}},
        traffic["tokens"], None, 0, traffic_gen.packed_lognormal)
    assert list(lens) != list(other.doc_lengths())  # another order
    assert sorted(lens) == sorted(other.doc_lengths())  # of the same lengths
    p = traffic["params"]
    assert lens.min() >= p["min"] * 0.8 and lens.max() <= p["max"]
    # heavy tail: the median document is far shorter than the mean
    assert np.median(lens) < 0.8 * lens.mean()
    # the reference check's smaller sample has the same shape of law
    _, small = _mask("packed", 0, tokens=4096)
    assert len(small.doc_lengths()) == len(lens)
    assert abs(flops.band_area(small) / 4096**2
               - flops.band_area(a) / a.tokens**2) < 0.01


def test_a_generator_that_does_not_fill_the_tokens_is_refused():
    traffic = {"generator": "short", "tokens": 100}
    with pytest.raises(ValueError, match="do not fill"):
        traffic_gen.make_mask(
            traffic, 100, None, 0, lambda params, tokens, scale, rng: [60, 30])
