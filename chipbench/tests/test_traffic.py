"""The key chooser: deterministic in the seed, and zipfian 0.99."""

import numpy as np

import traffic

CFG = {"recordcount": 100_000, "fieldcount": 10, "fieldlength": 100}
MIX = {"clients": 64, "read": 0.5, "update": 0.5, "distribution": "zipfian", "theta": 0.99}
BIG = 2 ** 31 + 11  # the driver's seeds pass 32 signed bits


def test_same_seed_same_traffic():
    a, b = traffic.Records(CFG, BIG), traffic.Records(CFG, BIG)
    assert a.pool == b.pool and (a.key_of_rank == b.key_of_rank).all()
    ua, ka = traffic.sequences(MIX, a, BIG, 500)
    ub, kb = traffic.sequences(MIX, b, BIG, 500)
    assert (ua == ub).all() and (ka == kb).all()


def test_another_seed_other_keys_same_work():
    a, b = traffic.Records(CFG, BIG), traffic.Records(CFG, BIG + 1)
    assert (a.key_of_rank != b.key_of_rank).mean() > 0.99
    assert a.n == b.n and a.valuebytes == b.valuebytes == 1000


def test_top_rank_share_is_zipfian_099():
    rec = traffic.Records(CFG, 7)
    upd, key = traffic.sequences(MIX, rec, 7, 20_000)   # 1.28 M draws
    expect = 1.0 / (1.0 / np.arange(1, 100_001) ** 0.99).sum()  # 0.0783
    share = (key == rec.key_of_rank[0]).mean()
    assert abs(share - expect) < 0.02 * expect + 3 * np.sqrt(expect / key.size)
    second = (key == rec.key_of_rank[1]).mean()
    assert abs(second / share - 2 ** -0.99) < 0.03
    assert abs(upd.mean() - 0.5) < 0.005


def test_uniform_mix_is_flat():
    rec = traffic.Records(CFG, 7)
    _, key = traffic.sequences({**MIX, "distribution": "uniform"}, rec, 7, 20_000)
    assert np.bincount(key.ravel(), minlength=rec.n).max() < 60  # mean 12.8


def test_values_carry_their_writer_and_rebuild_from_the_tag():
    rec = traffic.Records(CFG, 7)
    v = rec.value(63, 1234567)
    assert len(v) == 1000 and v.isascii()
    assert int(v[:traffic.TAG]) == traffic.code(63, 1234567)
    assert rec.value_of_code(int(v[:traffic.TAG])) == v
    load = rec.load_ops()
    assert len(load) == 100_000 and load[5] == ("Put", rec.keys[5], rec.value(traffic.LOADER, 5))
    assert len({val[:traffic.TAG] for _, _, val in load}) == 100_000


# -- the open loop's schedule -------------------------------------------------

OPEN = {"rate_ops_s": 1130}
BURST = {**OPEN, "burst": {"factor": 4.0, "duty": 0.2}}


def per_second(due, seconds):
    return np.bincount(due.astype(int), minlength=seconds)


def test_schedule_has_the_same_work_for_every_seed():
    for mix in (OPEN, BURST):
        a, b = traffic.arrivals(mix, BIG, 75.0), traffic.arrivals(mix, 7, 75.0)
        assert len(a) == len(b) == 1130 * 75                     # exact, whatever the seed
        for due in (a, b):
            assert (np.diff(due) >= 0).all() and due[0] >= 0.0 and due[-1] < 75.0
        assert (per_second(a, 75) == 1130).all() and (per_second(b, 75) == 1130).all()
        assert not np.array_equal(a, b)                           # two seeds: other instants
        assert np.array_equal(a, traffic.arrivals(mix, BIG, 75.0))   # one seed: bit for bit


def test_burst_puts_four_fifths_of_every_second_in_its_first_fifth():
    due = traffic.arrivals(BURST, BIG, 30.0)
    early = per_second(due[(due % 1.0) < 0.2], 30)
    assert (early == 904).all()                                   # 4,520 ops/s x 0.2 s
    assert (per_second(due, 30) - early == 226).all()             # 282.5 ops/s x 0.8 s
    steady = traffic.arrivals(OPEN, BIG, 30.0)
    assert abs(((steady % 1.0) < 0.2).mean() - 0.2) < 0.01


def test_schedule_looks_poisson_inside_a_stretch():
    gaps = np.diff(traffic.arrivals(OPEN, BIG, 60.0))
    assert abs(gaps.mean() * 1130 - 1.0) < 0.01
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05             # exponential: cv = 1


def test_schedule_rounds_a_rate_that_gives_no_whole_count_and_refuses_nonsense():
    due = traffic.arrivals({"rate_ops_s": 282.5}, 3, 10.5)
    assert len(due) == round(282.5 * 10.5) and due[-1] < 10.5
    counts = per_second(due, 11)
    assert set(counts[:10]) <= {282, 283} and counts[:10].sum() == 2825
    import pytest
    with pytest.raises(ValueError):
        traffic.arrivals({**OPEN, "burst": {"factor": 6.0, "duty": 0.2}}, 3, 5.0)
    with pytest.raises(ValueError):
        traffic.arrivals({"rate_ops_s": 0}, 3, 5.0)


def test_sequences_take_the_open_loops_rows():
    rec = traffic.Records(CFG, 7)
    upd, key = traffic.sequences({k: v for k, v in MIX.items() if k != "clients"}, rec, 7, 100,
                                 clients=64)
    assert upd.shape == key.shape == (64, 100)
    closed = traffic.sequences(MIX, rec, 7, 100)
    assert (closed[0] == upd).all() and (closed[1] == key).all()   # the same streams
