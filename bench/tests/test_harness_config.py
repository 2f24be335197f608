"""Every preset of the program loads back from a configuration file's JSON
as the ``ModelConfig`` it was written from."""
import dataclasses
import importlib
import json

import pytest

from bench import harness

from repro.configs import ALL_CONFIGS

PRESETS = [(name, fn) for name in ALL_CONFIGS
           for fn in ("config", "smoke_config")
           if hasattr(importlib.import_module(f"repro.configs.{name}"), fn)]


@pytest.mark.parametrize("name,fn", PRESETS,
                         ids=[f"{n}.{f}" for n, f in PRESETS])
def test_preset_round_trips_through_json(name, fn):
    cfg = getattr(importlib.import_module(f"repro.configs.{name}"), fn)()
    model = json.loads(json.dumps(dataclasses.asdict(cfg)))
    assert harness.model_config(model) == cfg


def test_unknown_key_fails_by_name():
    with pytest.raises(ValueError, match="model.moe.no_such_field"):
        harness.model_config({"moe": {"n_experts": 8, "no_such_field": 64}})
    with pytest.raises(ValueError, match="model.no_such_field"):
        harness.model_config({"no_such_field": 1})

