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
