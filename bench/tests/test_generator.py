"""The one traffic generator: the same seed gives the same batches, and
every mix file is parameters for it."""
import json

import numpy as np
import pytest

from bench import generator, spec


def small(mix, **kw):
    return {**mix, "batch": 256, "flows": 1000, **kw}


def test_same_seed_same_batches_any_seed_size():
    mix = small(generator.DEFAULTS)
    for seed in (0, 2**31 + 17, 2**40, -5):
        a, b = generator.Traffic(mix, seed), generator.Traffic(mix, seed)
        for k in (0, 7):
            x, y = a.batch(k), b.batch(k)
            for f in ("payload", "five_tuple", "length", "mask"):
                assert np.array_equal(x[f], y[f])
    one, two = generator.Traffic(mix, 1), generator.Traffic(mix, 2)
    assert not np.array_equal(one.batch(0)["five_tuple"],
                              two.batch(0)["five_tuple"])
    assert one.batch(0)["payload"].shape == two.batch(0)["payload"].shape


def test_patterns_embed_in_the_stated_share():
    mix = small(generator.DEFAULTS, embed_frac=0.25)
    pay = generator.Traffic(mix, 3).batch(0)["payload"]
    rows = [r for r in range(pay.shape[0])
            if b"attack" in pay[r].tobytes() or b"GET /admin" in pay[r].tobytes()]
    assert len(rows) >= 64 and set(range(64)) <= set(rows)


def test_the_flow_window_slides_and_blocked_flows_are_marked():
    mix = small(generator.DEFAULTS, slide_per_batch=500, telnet_flow_every=10,
                net192_flow_every=10)
    tr = generator.Traffic(mix, 9)
    f0, f4 = tr.flows(0), tr.flows(4)
    assert f0.min() >= 0 and f0.max() < 1000
    assert f4.min() >= 2000 and f4.max() < 3000
    five = tr.batch(0)["five_tuple"]
    assert np.array_equal(five[:, 3] == 23, f0 % 10 == 0)
    net = (five[:, 0].astype(np.int64) >> 24) & 0xFF
    assert np.array_equal(net == 0xC0, f0 % 10 == 5)
    assert set(net.tolist()) == {0x0A, 0xC0}


def test_flood_payloads_repeat_one_byte_in_the_stated_share():
    mix = small(generator.DEFAULTS, embed_frac=0.1, flood_frac=0.25)
    pay = generator.Traffic(mix, 6).batch(2)["payload"]
    flat = (pay == pay[:, :1]).all(axis=1)
    assert flat[-64:].all() and not flat[:-64].any()
    with pytest.raises(ValueError, match="flood_frac"):
        generator.Traffic(small(generator.DEFAULTS, embed_frac=0.8,
                                flood_frac=0.3), 1)


def test_unknown_traffic_keys_are_refused(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({"batch": 8, "bursts": 3}))
    with pytest.raises(ValueError, match="bursts"):
        generator.load_mix(path)


@pytest.mark.parametrize("lanes", [2, 8])
def test_lane_shapes_reach_both_lane_buckets(lanes):
    """The shape batches give a plane with 15% headroom its even lanes and
    one lane past B/lanes, so both lane buckets compile at set-up."""
    from repro.core.orchestrator import TrafficOrchestrator
    mix = small(generator.DEFAULTS)
    tr = generator.Traffic(mix, 4)
    to = TrafficOrchestrator(lanes, 1.15 * tr.B / lanes)
    from bench.harness import packets
    even, uneven = [np.bincount(to.partition_assign(packets(a)),
                                minlength=lanes).max()
                    for a in tr.lane_shapes(lanes)]
    assert even == tr.B // lanes
    assert uneven > tr.B // lanes


def test_every_mix_file_loads():
    for w in spec.load_benchmark()["workloads"]:
        mix = generator.load_mix(spec.BENCH / "traffic" / f"{w['traffic']}.json")
        assert mix["batch"] > 0 and mix["inflight"] >= 1
