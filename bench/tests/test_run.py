"""A whole run of each cell on the CPU at a tiny size, past the harness's
look for a chip: the plain run comes out correct, and the run comes out
not correct when the timed path is broken underneath it, or when the
control takes the program's place. No timing is asserted."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness, spec
from repro.apps import nf
from repro.core.executor import ParallelDataPlane

CELLS = ["isg.mtu1500", "fw.min64"]


def tiny(name, slide=0):
    """The cell at 128 packets a batch; ``slide`` moves its flow window."""
    cell = spec.resolve(name)
    cell.mix.update(batch=128, flows=1500, warmup_batches=2,
                    slide_per_batch=slide)
    return cell


def run(cell, seed=2**31 + 99):
    return harness.measure(cell, seed, 0.3, False, log=lambda _l: None)


@pytest.mark.parametrize("name,slide", [("isg.mtu1500", 0), ("fw.min64", 0),
                                        ("fw.min64", 150)])
def test_a_plain_run_is_correct(name, slide):
    cell = tiny(name, slide)
    out = run(cell)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {m.name for m in cell.end_to_end}
    assert all(v["value"] == 0 and v["limit"] == 0
               for v in out["check"].values())
    assert list(out)[-1] == "check"
    assert out["device"]["platform"] == "cpu"


def _chain_skipped(self, batch, tenant=None):
    """A step that returns its state unchanged: the egress is the ingress."""
    self._orig_process(batch, tenant)
    return jax.tree.map(jax.numpy.asarray, batch)


def _half_left_out(self, batch, tenant=None):
    out = self._orig_process(batch, tenant)
    return jax.tree.map(lambda a: a[: a.shape[0] // 2], out)


def _one_byte_altered(self, batch, tenant=None):
    out = self._orig_process(batch, tenant)
    return out.__class__(payload=out.payload.at[5, 7].add(1),
                         length=out.length, five_tuple=out.five_tuple,
                         mask=out.mask, meta=out.meta)


@pytest.mark.parametrize("fault", [_chain_skipped, _half_left_out,
                                   _one_byte_altered])
@pytest.mark.parametrize("name", ["isg.mtu1500", "fw.min64"])
def test_a_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    monkeypatch.setattr(ParallelDataPlane, "_orig_process",
                        ParallelDataPlane.process, raising=False)
    monkeypatch.setattr(ParallelDataPlane, "process", fault)
    out = run(tiny(name))
    assert out["correct"] is False
    assert out["failed"] >= 1
    assert any(v["value"] > v["limit"] for v in out["check"].values())


def _keeps_every_packet(pkt):
    """A DDoS check that drops nothing."""
    return jnp.ones(pkt.payload.shape[0], bool)


_FIREWALL = nf.firewall


def _firewall_without_the_192_rule():
    app = _FIREWALL()
    app.stages[0] = dataclasses.replace(
        app.stages[0], ucf=lambda pkt: pkt.five_tuple[:, 3] != 23)
    return app


@pytest.mark.parametrize("name,attr,planted", [
    ("isg.mtu1500", "ddos_check", _keeps_every_packet),
    ("fw.min64", "firewall", _firewall_without_the_192_rule)])
def test_a_verdict_left_out_is_not_correct(name, attr, planted, monkeypatch):
    """Each cell's traffic makes its chain drop packets, so a verdict stage
    that keeps them fails ``mask``."""
    monkeypatch.setattr(nf, attr, planted)
    out = run(tiny(name))
    assert out["correct"] is False
    assert out["check"]["mask"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(name):
    cell = tiny(name)
    for seed in (1, 2, 2**31 + 3):
        out = control.read(cell, seed)
        assert out["correct"] is False
        assert out["check"]["payload"]["value"] > 0 or \
            out["check"]["five_tuple"]["value"] > 0


def test_the_control_keeps_every_packet_and_only_reorders():
    cell = tiny("fw.min64")
    from bench import generator
    arrays = generator.Traffic(cell.mix, 5).batch(3)
    got = control.control_egress(cell, arrays)
    want = cell.reference(arrays)
    assert got["payload"].shape == want["payload"].shape
    order = control.lane_order(arrays, 8)
    assert np.array_equal(got["five_tuple"], want["five_tuple"][order])
    assert sorted(order.tolist()) == list(range(arrays["payload"].shape[0]))
