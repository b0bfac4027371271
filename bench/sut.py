"""The system under test: the program's engine, built from a config file.

This is the only module of the benchmark that imports the program
(``src/repro``). The configuration's model file
(``bench/models/<equations>.py``) maps it onto the program's
``ModelConfig``; the engine is one ``ContinuousBatchingEngine`` on the
paged backend with the program's defaults, except the options the file's
``engine`` object gives (slots, ``max_len`` and page size, at least).
"""

from __future__ import annotations

import sys
import time

from bench import spec

sys.path.insert(0, str(spec.ROOT / "src"))

from repro.models.config import ModelConfig  # noqa: E402
from repro.serve.engine import ContinuousBatchingEngine, Request  # noqa: E402

__all__ = ["ContinuousBatchingEngine", "Request", "build_engine",
           "model_config"]


def model_config(cfg: dict) -> ModelConfig:
    return ModelConfig(**spec.model(cfg).program_config(cfg))


def build_engine(cfg: dict, params) -> ContinuousBatchingEngine:
    return ContinuousBatchingEngine(model_config(cfg), params,
                                    clock=time.perf_counter, **cfg["engine"])
