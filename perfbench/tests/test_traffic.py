import json
import os

import numpy as np
import pytest

from perfbench.harness import traffic


@pytest.fixture(scope="module")
def mix(root):
    return traffic.load_mix(os.path.join(root, "perfbench"), "chat-backlog")


def cycle(mix, seed, n=64, first_wave=0):
    s = traffic.RequestStream(mix, seed, 50257, first_wave=first_wave)
    return [s.next() for _ in range(n)]


def test_multiset_is_the_same_for_two_seeds_and_its_order_is_not(mix):
    a, b = cycle(mix, 1), cycle(mix, 2 ** 31 + 12345)
    la = [(len(p), o) for p, o in a]
    lb = [(len(p), o) for p, o in b]
    assert sorted(la) == sorted(lb) == sorted(traffic.length_multiset(mix))
    assert la != lb
    # token for token the same total work over one cycle
    assert sum(len(p) for p, _ in a) == sum(len(p) for p, _ in b)
    assert sum(o for _, o in a) == sum(o for _, o in b)
    # the same seed gives the same inputs
    again = cycle(mix, 1)
    assert all(np.array_equal(p, q) and o == r
               for (p, o), (q, r) in zip(a, again))


def test_second_cycle_is_the_multiset_again_in_another_order(mix):
    s = traffic.RequestStream(mix, 7, 50257, first_wave=0)
    one = [(len(p), o) for p, o in (s.next() for _ in range(64))]
    two = [(len(p), o) for p, o in (s.next() for _ in range(64))]
    assert sorted(one) == sorted(two) and one != two


def test_lengths_follow_the_files_quantiles_and_clips(mix):
    pairs = traffic.length_multiset(mix)
    prompts = sorted(p for p, _ in pairs)
    outputs = sorted(o for _, o in pairs)
    assert prompts[0] >= 16 and prompts[-1] <= 512
    assert outputs[0] >= 8 and outputs[-1] <= 256
    assert 90 <= prompts[32] <= 102  # the median sits at the file's
    assert 60 <= outputs[32] <= 68
    assert all(p + o <= 1024 for p, o in pairs)


def test_first_wave_has_staggered_residual_lives(mix):
    full = dict()
    wave = cycle(mix, 3, first_wave=64)
    plain = cycle(mix, 3, first_wave=0)
    # the same seed draws the same order; only the first wave's outputs are cut
    assert [len(p) for p, _ in wave] == [len(p) for p, _ in plain]
    fractions = [w[1] / p[1] for w, p in zip(wave, plain)]
    assert all(0 < f <= 1 for f in fractions)
    assert all(w[1] >= 1 for w in wave)
    # spread over the whole life, not bunched: every quarter is populated
    hist = np.histogram(fractions, bins=4, range=(0, 1))[0]
    assert hist.min() >= 6, hist
    assert len(set(w[1] for w in wave)) > 20
    # the request after the first wave is whole again
    s = traffic.RequestStream(mix, 3, 50257, first_wave=2)
    s.next(), s.next()
    assert s.next()[1] in {o for _, o in traffic.length_multiset(mix)}


def test_token_ids_avoid_the_pad_id_and_fit_the_vocabulary(mix):
    for p, _ in cycle(mix, 5, n=16):
        assert p.dtype == np.int32 and p.min() >= 1 and p.max() < 50257


def test_a_bad_pairing_is_refused(mix):
    bad = dict(mix, pairing=[0] * 64)
    with pytest.raises(ValueError):
        traffic.length_multiset(bad)


def test_record_split_is_written_once(tmp_path, root):
    pytest.importorskip("pytorch_distributed_tpu")
    mix = json.load(open(os.path.join(
        root, "perfbench", "unshipped", "resnet50.ddp4", "traffic",
        "ddp4.json")))
    mix = dict(mix, **mix["tiny"])
    d = traffic.ensure_record_split(mix, str(tmp_path / "records"))
    path = os.path.join(d, "train.rawtprc")
    stat = os.stat(path)
    assert stat.st_size > mix["records"] * mix["stored_px"] ** 2 * 3
    os.utime(path, ns=(1, 1))  # a second run must not rewrite it
    traffic.ensure_record_split(mix, str(tmp_path / "records"))
    assert os.stat(path).st_mtime_ns == 1
    # another size is another split: rewritten
    traffic.ensure_record_split(dict(mix, records=mix["records"] + 1),
                                str(tmp_path / "records"))
    assert os.stat(path).st_mtime_ns != 1


def test_corpus_comes_from_the_seed():
    mix = {"sequences": 8, "seq_len": 16}
    a = traffic.token_corpus(mix, 2 ** 31 + 5, 128)
    b = traffic.token_corpus(mix, 2 ** 31 + 5, 128)
    c = traffic.token_corpus(mix, 6, 128)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 1 and a.max() < 128 and len(a) == 128
