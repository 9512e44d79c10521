"""The exact comparison that decides ``correct``, on the references'
own outputs at a small size."""
import numpy as np
import pytest

from bench import check, generator, spec


def tiny_batch(cell, k=3, seed=2**31 + 5):
    mix = dict(cell.mix, batch=64, flows=500)
    return generator.Traffic(mix, seed).batch(k)


@pytest.fixture(params=["isg.mtu1500", "fw.min64"])
def cell(request):
    return spec.resolve(request.param)


def test_the_reference_agrees_with_itself(cell):
    arrays = tiny_batch(cell)
    counts = check.mismatches(cell.reference(arrays), cell.reference(arrays))
    assert set(counts.values()) == {0}
    assert {"payload", "length", "five_tuple", "mask", "missing"} <= set(counts)


def test_one_flipped_byte_of_the_egress_fails(cell):
    arrays = tiny_batch(cell)
    want = cell.reference(arrays)
    got = cell.reference(arrays)
    got["payload"] = got["payload"].copy()
    got["payload"][17, 3] ^= 0x01
    counts = check.mismatches(got, want)
    assert counts["payload"] == 1
    assert sum(counts.values()) == 1


def test_each_cells_traffic_makes_its_chain_drop_packets(cell):
    """The verdicts are checked both ways: ISG drops its flood rows by the
    entropy check and FW its port-23 and 192/8 flows; the rest are kept."""
    arrays = generator.Traffic(dict(cell.mix, batch=2048), 7).batch(30)
    mask = cell.reference(arrays)["mask"]
    if cell.name == "isg.mtu1500":
        flood = (arrays["payload"] == arrays["payload"][:, :1]).all(axis=1)
        assert flood.sum() == 20 and np.array_equal(mask, ~flood)
    else:
        five = arrays["five_tuple"]
        port23 = five[:, 3] == 23
        net192 = ((five[:, 0] >> 24) & 0xFF) == 0xC0
        assert port23.any() and net192.any()
        assert np.array_equal(mask, ~(port23 | net192))


def test_lost_packets_and_meta_keys_count():
    cell = spec.resolve("fw.min64")
    arrays = tiny_batch(cell)
    want = cell.reference(arrays)
    half = {**{f: want[f][:32] for f in check.FIELDS},
            "meta": {k: v[:32] for k, v in want["meta"].items()}}
    assert check.mismatches(half, want)["missing"] == 32
    bare = {**{f: want[f] for f in check.FIELDS}, "meta": {}}
    assert check.mismatches(bare, want)["meta.conn_pkts"] == 64


def test_check_batches_sums_over_the_sample_with_limit_zero():
    cell = spec.resolve("fw.min64")
    mix = dict(cell.mix, batch=64, flows=500)
    traffic = generator.Traffic(mix, 11)
    got = {k: cell.reference(traffic.batch(k)) for k in (4, 9)}
    got[9]["mask"] = ~got[9]["mask"]
    numbers, failed = check.check_batches(got, traffic, cell.reference)
    assert failed == 1
    assert numbers["mask"] == {"value": 64, "limit": 0}
    assert numbers["payload"] == {"value": 0, "limit": 0}
    _, failed = check.check_batches({}, traffic, cell.reference)
    assert failed == 1


def test_the_isg_reference_matches_the_program_oracles_on_a_slice():
    import jax.numpy as jnp
    from repro.apps.nf import ipsec_gateway
    from repro.core.graph import PacketBatch, run_pipeline
    cell = spec.resolve("isg.mtu1500")
    arrays = generator.Traffic(dict(cell.mix, batch=256, flows=500),
                               2**31 + 5).batch(3)
    app = ipsec_gateway(cell.config["app_args"]["rules"], impl="ref")
    out = run_pipeline(app, PacketBatch(
        payload=jnp.asarray(arrays["payload"]),
        length=jnp.asarray(arrays["length"]),
        five_tuple=jnp.asarray(arrays["five_tuple"]),
        mask=jnp.asarray(arrays["mask"]), meta={}))
    counts = check.mismatches(check.to_host(out), cell.reference(arrays))
    assert set(counts.values()) == {0}
    assert cell.reference(arrays)["meta"]["match_num"].sum() > 0
    assert (~cell.reference(arrays)["mask"]).sum() == 2
