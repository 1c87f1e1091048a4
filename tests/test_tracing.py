"""The names the train step carries (``repro.tracing``): the scopes on the
compiled SMOKE DiT step's ops, and the trainer's host spans, step
annotation and recompile record under the profiler on the CPU.  (The
planned switches' ``dsp_switch`` scope is pinned on simulated devices in
tests/test_hlo_collectives.py, and on a described v5e in
tests/test_tpu_compile.py.)"""
import glob
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_INSTR = re.compile(r"\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?:\([^=]*?\)|\S+)\s+"
                    r"([\w\-]+)\(")


def _ops(hlo):
    """(opcode, op_name) of every instruction of a compiled module's text."""
    out = []
    for ln in hlo.splitlines():
        m = _INSTR.match(ln)
        if m:
            name = re.search(r'op_name="([^"]*)"', ln)
            out.append((m.group(1), name.group(1) if name else "", ln))
    return out


def _backward(op_name):
    return ("transpose(" in op_name
            and "rematted_computation" not in op_name)


@pytest.fixture(scope="module")
def smoke_step_hlo():
    from repro.launch.train import build, parse_args
    trainer, _ = build(parse_args(
        ["--arch", "transformer2d-720m", "--batch", "1", "--temporal", "4",
         "--spatial", "16", "--steps", "2"]))
    batch = trainer.data_fn(0)
    return trainer.step_fn.lower(trainer.params, trainer.opt_state,
                                 batch).compile().as_text()


def test_every_matmul_of_the_step_carries_a_stage_scope(smoke_step_hlo):
    from repro.analysis.roofline import op_scope
    ops = _ops(smoke_step_hlo)
    heavy = [(op, name, ln) for op, name, ln in ops
             if op in ("dot", "convolution", "custom-call")]
    assert len(heavy) > 20
    unscoped = [name for _, name, ln in heavy if not op_scope(ln)]
    assert not unscoped, unscoped
    scopes = {op_scope(ln) for _, _, ln in heavy}
    assert {"adaln", "proj", "attn", "mlp", "attn_bwd", "embed",
            "loss"} <= scopes, scopes


def test_attention_backward_and_optimizer_carry_their_scopes(smoke_step_hlo):
    from repro.analysis.roofline import op_scope
    ops = [(op, name, op_scope(ln)) for op, name, ln in _ops(smoke_step_hlo)
           if name]
    # attention's backward matmuls (scores again, dp, dq, dk, dv) run in
    # the custom_vjp rule; the layout transposes around it stay ``attn``
    attn_bwd = [(name, s) for op, name, s in ops
                if op == "dot" and "/attn/" in name and _backward(name)]
    assert len(attn_bwd) >= 2 * 4
    assert all(s == "attn_bwd" for _, s in attn_bwd), [
        name for name, s in attn_bwd if s != "attn_bwd"]
    # the forward (and remat's recompute) of attention is not attn_bwd
    assert any(s == "attn" for op, name, s in ops
               if op == "dot" and not _backward(name))
    # outside the differentiated loss the step runs AdamW and the part of
    # the forward that needs no derivative (the adaLN input); every sqrt
    # (Adam's denominators, the clip's global norm) is AdamW's (a
    # reduction's own computation names only its op: left out)
    outside = [(name, s) for _, name, s in ops
               if name.startswith("jit(step)/") and "jvp(" not in name]
    assert sum(s == "adamw" for _, s in outside) > 20
    assert all(s in ("adamw", "adaln", "embed") for _, s in outside), [
        name for name, s in outside if s not in ("adamw", "adaln", "embed")]
    sqrt = [s for _, name, s in ops if name.endswith("/sqrt")]
    assert sqrt and set(sqrt) == {"adamw"}


@pytest.fixture(scope="module")
def wide_frame_step_hlo():
    """The SMOKE step (two layers) at T=4, S=256: temporal attention's keys
    fit one KV block, spatial attention's span two."""
    from repro.launch.train import build, parse_args
    trainer, _ = build(parse_args(
        ["--arch", "transformer2d-720m", "--batch", "1", "--temporal", "4",
         "--spatial", "256", "--steps", "2"]))
    batch = trainer.data_fn(0)
    return trainer.step_fn.lower(trainer.params, trainer.opt_state,
                                 batch).compile().as_text()


def test_attention_forward_runs_the_kernel_only_past_one_kv_block(
        wide_frame_step_hlo):
    from repro import tracing
    from repro.analysis.roofline import op_scope

    def kernel(name, ln):     # the Pallas call (interpreted here) or its op
        return "flash_fwd" in name or "tpu_custom_call" in ln

    fwd = {}
    for axis in (tracing.TEMPORAL, tracing.SPATIAL):
        fwd[axis] = [(op, name, ln) for op, name, ln in _ops(wide_frame_step_hlo)
                     if f"/{axis}/{tracing.ATTN}/" in name
                     and tracing.ATTN_BWD not in name and not _backward(name)]
    # temporal: XLA's fused attention, no kernel; only the layout
    # transposes around it (models/transformer2d.py) sit outside attn_xla
    temporal = fwd[tracing.TEMPORAL]
    assert not [name for _, name, ln in temporal if kernel(name, ln)]
    assert not [name for _, name, _ in temporal
                if f"/{tracing.ATTN_XLA}/" not in name
                and not name.endswith("/transpose")]
    assert sum(op == "dot" for op, _, _ in temporal) >= 2 * 2
    # the dots still read as the stage ``attn``
    assert {op_scope(ln) for op, _, ln in temporal if op == "dot"} == {
        tracing.ATTN}
    # spatial: the flash kernel, and nothing of the XLA path
    spatial = fwd[tracing.SPATIAL]
    assert any(kernel(name, ln) for _, name, ln in spatial)
    assert not [name for _, name, _ in spatial if tracing.ATTN_XLA in name]


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    profile = ProfileData.from_file(path)
    host = [e for p in profile.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    return profile, sorted(host, key=lambda e: e.start_ns)


def test_trainer_spans_steps_and_recompiles_under_the_profiler(tmp_path):
    import jax
    import jax.numpy as jnp
    from chipbench import trace
    from repro import tracing
    from repro.optim.adamw import OptConfig
    from repro.train.trainer import Trainer, TrainerConfig

    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"]) ** 2), {}

    rows = {3: 3}       # step 3 brings a batch of a new shape: a re-jit

    def data_fn(step):
        return {"x": np.ones((rows.get(step, 2), 4), np.float32)}

    steps = 5
    trainer = Trainer(
        loss_fn=loss_fn, params={"w": jnp.ones((4, 4), jnp.float32)},
        opt_cfg=OptConfig(warmup_steps=1, total_steps=steps),
        cfg=TrainerConfig(total_steps=steps, log_every=1, ckpt_every=2),
        data_fn=data_fn, ckpt_dir=str(tmp_path / "ckpt"))
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        out = trainer.run()
    finally:
        jax.profiler.stop_trace()

    assert out["recompiles"] == trainer.recompiles
    assert [s for s, _ in trainer.recompiles] == [3]
    assert all(n >= 1 for _, n in trainer.recompiles)

    profile, host = _host_events(trace_dir)
    marks = [e for e in host if e.name == tracing.STEP]
    assert [dict(e.stats)["step_num"] for e in marks] == list(range(steps))
    spans = (tracing.DATA, tracing.DISPATCH, tracing.SYNC)
    for mark in marks:
        inside = [e.name for e in host if e.name in spans
                  and mark.start_ns <= e.start_ns
                  and e.end_ns <= mark.end_ns]
        assert inside == list(spans), inside
    # saves at steps 2 and 4, and the blocking one at the end
    assert sum(e.name == tracing.CHECKPOINT for e in host) == 3
    # the benchmark's reduction picks the same spans up
    picked = [e.name for e in trace.events_from_profile(profile, 1)
              if e.where == "host"]
    assert picked == list(spans) * steps
