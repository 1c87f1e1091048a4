"""The four-chip cell's collective readers (``chipbench.collectives``) on
one recorded step of the 3B cell on a TPU v5e 2x2, cut down by
``scopes.trim``; and on the one-chip 720M step, where they find nothing."""
import os
import types

import pytest

from chipbench import collectives, scopes
from chipbench.harness import Benchmark

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "t2d-3b.train-sp4.16x1024.scopes.textproto.gz")
ONE_CHIP = os.path.join(DATA, "t2d-720m.train.16x256.scopes.textproto.gz")
READERS = ("dsp_switch_ms_per_step", "dsp_switch_exposed_ms_per_step",
           "zero_collectives_ms_per_step")


def _read(monkeypatch, path, chips):
    monkeypatch.setattr(scopes, "trace_file", lambda: path)
    m = types.SimpleNamespace(chips=chips, steps=1)
    bench = Benchmark()
    return {name: bench.reader(name).read(m) for name in READERS}, m


def test_readers_on_the_recorded_sharded_step(monkeypatch):
    got, m = _read(monkeypatch, RECORDED, 4)
    assert all(isinstance(v, float) and v > 0 for v in got.values()), got
    assert got["dsp_switch_exposed_ms_per_step"] <= \
        got["dsp_switch_ms_per_step"] * (1 + 1e-12)
    every = scopes.ms_per_step(m, collectives.is_collective)
    assert got["dsp_switch_ms_per_step"] + got[
        "zero_collectives_ms_per_step"] == pytest.approx(every, rel=1e-12)


def test_the_recorded_switches_are_the_planned_ones():
    """Per chip and step of the 18 block pairs: 2 forward switches a pair,
    remat's recompute of 3 of the 4 in each group of two pairs (the
    group's last is its saved carry), and one switch on each block-end
    anchor in the backward."""
    found = [op for op in scopes.ops(RECORDED, 4)
             if op.chip == "0" and collectives.is_switch(op)]
    legs = {}
    for op in found:
        leg = ("bwd" if op.backward else "recompute"
               if "rematted_computation" in op.op_name else "fwd")
        legs[leg] = legs.get(leg, 0) + 1
    assert legs == {"fwd": 36, "recompute": 27, "bwd": 36}


def test_readers_find_nothing_on_one_chip(monkeypatch):
    got, _ = _read(monkeypatch, ONE_CHIP, 1)
    assert got == dict.fromkeys(READERS)
