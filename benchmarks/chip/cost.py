"""Per-architecture operation and byte counts, found by the configuration's
``model_type`` in ``costs/<model_type>.py``; each module gives
``step_cost(cfg, ctxs) -> (flops, bytes)``."""

from __future__ import annotations

import importlib.util
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_").replace(
            "-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def step_cost_for(cfg: dict):
    mod = load_module(HERE / "costs" / f"{cfg['model_type']}.py")
    return lambda ctxs: mod.step_cost(cfg, ctxs)
