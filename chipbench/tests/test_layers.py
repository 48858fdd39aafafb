"""Layer readers: deltas of what was scraped; no event means no number."""

import layers

G = layers.Gathered(
    10.0,
    {"ticks": 100, "wal.appends": 10, "wal.fsyncs": 5, "idle": 3},
    {"ticks": 1100, "wal.appends": 410, "wal.fsyncs": 105, "idle": 3},
    {"a_s": {"n": 10, "sum": 1.0}, "b_s": {"n": 5, "sum": 5.0}},
    {"a_s": {"n": 30, "sum": 1.5}, "b_s": {"n": 5, "sum": 5.0}, "c_s": {"n": 2, "sum": 0.5}},
    {"cpu_share": 42.0}, {"tick_ms": 2.5},
)


def rd(**reader):
    return layers.read({"name": "x", "reader": reader}, G)


def test_readers():
    assert rd(kind="counter_delta", counter="ticks") == (1000.0, "")
    assert rd(kind="counter_rate", counter="ticks") == (100.0, "")
    assert rd(kind="counter_ratio", num="wal.appends", den="wal.fsyncs") == (4.0, "")
    assert rd(kind="hist_mean", hists="a_s", scale=1000.0) == (25.0, "")
    assert rd(kind="hist_mean", hists=["a_s", "c_s"], scale=1.0) == (0.025 + 0.25, "")
    assert rd(kind="hist_sum", hist="c_s") == (0.5, "")
    assert rd(kind="client", field="cpu_share") == (42.0, "")
    assert rd(kind="trace", field="tick_ms") == (2.5, "")


def test_no_event_in_the_window_is_no_number_never_zero():
    for reader in (
        dict(kind="counter_delta", counter="idle"),
        dict(kind="counter_rate", counter="never-seen"),
        dict(kind="counter_ratio", num="ticks", den="idle"),
        dict(kind="hist_mean", hists="b_s"),
        dict(kind="hist_mean", hists=["a_s", "b_s"]),
        dict(kind="hist_sum", hist="never-seen"),
        dict(kind="trace", field="kernel_roofline"),
    ):
        value, why = rd(**reader)
        assert value is None and why


def test_a_share_of_a_whole_that_grew_reads_zero_where_its_part_did_not():
    share = dict(kind="counter_ratio", num="shed", den=["shed", "ticks"], scale=100.0)
    assert rd(**share) == (0.0, "")                 # nothing shed of 1,000: 0 %
    assert rd(**{**share, "num": "wal.appends", "den": ["wal.appends", "wal.fsyncs"]}) == (80.0, "")
    value, why = rd(**{**share, "den": ["shed", "idle"]})
    assert value is None and why                    # the whole did not grow: nothing to read


def test_a_gauge_of_the_restarted_server():
    gauge = dict(kind="restart_gauge", gauge="ready.replay_s")
    value, why = rd(**gauge)
    assert value is None and why                    # no restart in this run
    g = layers.Gathered(10.0, {}, {}, {}, {}, {}, {}, {"ready.replay_s": 2.5, "ready.elect_s": 0.0})
    assert layers.read({"name": "x", "reader": gauge}, g) == (2.5, "")
    value, why = layers.read({"name": "x", "reader": {**gauge, "gauge": "ready.nothing_s"}}, g)
    assert value is None and why


def test_a_counter_per_operation_of_the_window():
    g = layers.Gathered(10.0, {"shard.wrong_group": 5, "same": 7}, {"shard.wrong_group": 25, "same": 7},
                        {}, {}, {"completed": 400}, {})
    per_op = dict(kind="counter_per_op", counter="shard.wrong_group", scale=100.0)
    assert layers.read({"name": "x", "reader": per_op}, g) == (5.0, "")
    assert layers.read({"name": "x", "reader": {**per_op, "counter": "same"}}, g) == (0.0, "")
    for reader, gathered in (({**per_op, "counter": "never-seen"}, g),
                             (per_op, layers.Gathered(10.0, {}, {"shard.wrong_group": 3}, {}, {},
                                                      {}, {}))):
        value, why = layers.read({"name": "x", "reader": reader}, gathered)
        assert value is None and why
