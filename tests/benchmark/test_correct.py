"""What decides ``correct`` comes out false for the control and for each
fault a cell can have, with the timed path broken underneath the harness,
on the CPU at a tiny size.

The control is the reference put in the program's place and computed in
float8 (the precision below the configurations' bf16). In the copy cells
the faults are planted in the runtime's channel drain: a drain that leaves
its destination unchanged, one that moves only the first half of its
descriptors, and one that alters one element of a row it wrote. In the
serve cell they are planted in the engine's decode step: a step that
returns its cache state unchanged, and one whose logits are altered so
that the token it produces is another. No cell runs across chips, so no
exchange can be left out; half of a batch left out, as a mean over the
rest, is a training fault.
"""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from repro.core.descriptor import DescriptorArray
from repro.runtime import channel as channel_mod
from repro.serve import engine as engine_mod
from test_harness import _run
from conftest import TINY_KV, TINY_ROWS, TINY_SERVE


def _checks(out):
    line = json.loads(out.strip().splitlines()[-1])
    return line["correct"], {k: v["value"] for k, v in line["checks"].items()}


@pytest.mark.parametrize("workload",
                         [TINY_KV, TINY_ROWS, TINY_SERVE])
def test_control_is_not_correct(checkout, workload):
    seconds = 1.0 if workload == TINY_SERVE else 0.3
    rc, out, _ = _run(checkout, workload, control=True, seconds=seconds)
    correct, checks = _checks(out)
    assert rc == 0 and correct is False
    assert checks.get("rows_wrong", 0) > 0 \
        or checks.get("served_gap_max", 0) > 0.03


def _unchanged(orig):
    def drain_one(self, pools):
        b = self.pending[0] if self.pending else None
        before = pools[b.dst_pool] if b else None
        ran = orig(self, pools)
        if b is not None:
            pools[b.dst_pool] = before
        return ran
    return drain_one


def _half(orig):
    def drain_one(self, pools):
        if self.pending:
            b = self.pending[0]
            d = b.descs
            h = max(d.num_descriptors // 2, 1)
            b.descs = DescriptorArray.create(
                np.asarray(d.src)[:h], np.asarray(d.dst)[:h],
                np.asarray(d.length)[:h], config=np.asarray(d.config)[:h])
        return orig(self, pools)
    return drain_one


def _altered(orig):
    def drain_one(self, pools):
        b = self.pending[0] if self.pending else None
        ran = orig(self, pools)
        if b is not None:
            out = pools[b.dst_pool]
            row = out.size // out.shape[0] if out.ndim > 1 else 1
            k = int(np.asarray(b.descs.dst)[0]) * row
            flat = jax.lax.bitcast_convert_type(out.reshape(-1),
                                                jax.numpy.uint16)
            flat = flat.at[k].set(flat[k] ^ 1)
            pools[b.dst_pool] = jax.lax.bitcast_convert_type(
                flat, out.dtype).reshape(out.shape)
        return ran
    return drain_one


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered])
@pytest.mark.parametrize("workload", [TINY_KV, TINY_ROWS])
def test_fault_is_not_correct(checkout, monkeypatch, workload, fault):
    orig = channel_mod.Channel.drain_one
    monkeypatch.setattr(channel_mod.Channel, "drain_one", fault(orig))
    rc, out, _ = _run(checkout, workload)
    correct, checks = _checks(out)
    assert rc == 0 and correct is False
    assert checks["rows_wrong"] > 0



def _stale_state(step_fn):
    def run(params, tokens, state):
        _, new = step_fn(params, tokens, state)
        logits, _ = step_fn(params, tokens, state)
        return logits, new._replace(caches=state.caches)
    return run


def _other_token(step_fn):
    def run(params, tokens, state):
        logits, new = step_fn(params, tokens, state)
        return jax.numpy.roll(logits, 1, axis=-1), new
    return run


@pytest.mark.parametrize("fault", [_stale_state, _other_token])
def test_serve_fault_is_not_correct(checkout, monkeypatch, fault):
    orig = engine_mod.ServeEngine.__init__

    def init(self, *a, **kw):
        orig(self, *a, **kw)
        self._step_fn = fault(self._step_fn)

    monkeypatch.setattr(engine_mod.ServeEngine, "__init__", init)
    rc, out, _ = _run(checkout, TINY_SERVE, seconds=1.0)
    correct, checks = _checks(out)
    assert rc == 0 and correct is False
    assert checks["served_gap_max"] > 0.03
