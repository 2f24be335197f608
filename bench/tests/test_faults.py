"""The check sees a broken timed path: each fault a cell can have, planted
under a run that skips only the look for a chip, turns ``correct`` false,
while the sound path stays correct under the same limits."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.drivers import serve, train
from bench.tests.conftest import TINY

SERVE_MIX = {"driver": "serve", "rate_per_s": 6.0, "slots": 4, "max_len": 512,
             "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                        "min": 20, "max": 100},
             "output": {"dist": "uniform", "min": 8, "max": 24},
             "drain_s": 30.0, "check": {"requests": 4, "min_tokens": 30}}
TRAIN_MIX = {"driver": "train", "batch": 4, "seq": 64, "distinct_batches": 4,
             "optimizer": {"kind": "adamw", "b1": 0.9, "b2": 0.95,
                           "eps": 1e-8, "weight_decay": 0.1, "grad_clip": 1.0,
                           "peak_lr": 3e-4, "warmup": 5,
                           "total_steps": 10000}}


def _run(mix, model, cell, seconds=1.5):
    limits = harness.load_json(harness.BENCH / "limits" / f"{cell}.json")
    r = harness.Run(cell={"name": cell},
                    config={"model": model, "reference": "flow_lm"}, mix=mix,
                    limits=limits, seed=2**33 + 11, seconds=seconds,
                    trace_dir=None, t0=time.perf_counter(),
                    devices=jax.devices())
    out = (serve if mix["driver"] == "serve" else train).run(r)
    return harness.judge(out.checks, out.complete), out


def _alter_tokens(monkeypatch):
    from repro.serving.worker import Worker

    real = Worker.step

    def step(self, tokens, pos, temps, live):
        out = real(self, tokens, pos, temps, live)
        return np.where(live, (out + 1) % self.cfg.vocab_size, out)

    monkeypatch.setattr(Worker, "step", step)


def _freeze_decode_state(monkeypatch):
    from repro.serving.worker import Worker

    real = Worker.step

    def step(self, tokens, pos, temps, live):
        before = jax.tree.map(jnp.copy, self.caches)
        out = real(self, tokens, pos, temps, live)
        self.caches = before
        return out

    monkeypatch.setattr(Worker, "step", step)


def _wrap_train_step(monkeypatch, fault):
    import repro.launch.steps as steps

    real_build = steps.build_train_step

    def build(*a, **k):
        jit_step, shape, specs, plan = real_build(*a, **k)

        def broken(state, batch):
            if fault == "unchanged":
                _, met = jit_step(jax.tree.map(jnp.copy, state), batch)
                return state, met
            half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
            return jit_step(state, half)

        broken.lower = jit_step.lower
        return broken, shape, specs, plan

    monkeypatch.setattr(steps, "build_train_step", build)


SERVE_CELL = "granite8b-flow.chat"
TRAIN_CELL = "flowformer-lm-xla.train-8k"
TRAIN_MODEL = dict(TINY, act="gelu", norm="layernorm", n_kv_heads=4)


def test_sound_serving_is_correct():
    ok, out = _run(SERVE_MIX, TINY, SERVE_CELL)
    assert ok, out.checks


@pytest.mark.parametrize("fault", [_alter_tokens, _freeze_decode_state],
                         ids=["token_altered", "decode_state_unchanged"])
def test_serving_fault_is_caught(monkeypatch, fault):
    fault(monkeypatch)
    ok, out = _run(SERVE_MIX, TINY, SERVE_CELL)
    assert not ok, out.checks


def test_sound_training_is_correct():
    ok, out = _run(TRAIN_MIX, TRAIN_MODEL, TRAIN_CELL)
    assert ok, out.checks


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_training_fault_is_caught(monkeypatch, fault):
    _wrap_train_step(monkeypatch, fault)
    ok, out = _run(TRAIN_MIX, TRAIN_MODEL, TRAIN_CELL)
    assert not ok, out.checks
