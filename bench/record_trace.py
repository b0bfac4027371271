"""Record the small chip trace that the trace-reduction test reads.

  python3 -m bench.record_trace tests/benchmark/data

On a TPU: the granite-3-2b configuration cut by ``small_config`` serves
four requests through the engine; three engine steps are traced with the
harness's own spans, and the trace is written to
``<dir>/engine_steps.xplane.pb``.
"""

from __future__ import annotations

import pathlib
import shutil
import sys
import tempfile
import time


def small_config(cfg: dict) -> dict:
    """``cfg`` cut to 4 layers of width 128 and 4 slots of 128 tokens: a
    size the CPU tests and the recorded trace share."""
    return dict(cfg, num_hidden_layers=4, hidden_size=128,
                num_attention_heads=4, num_key_value_heads=2,
                intermediate_size=256, vocab_size=512,
                attention_multiplier=32 ** -0.5,
                engine={"slots": 4, "max_len": 128, "page_size": 16})


def main(out_dir: str) -> int:
    import jax

    from bench import harness, spec, sut, weights
    from bench import trace as trace_mod
    from bench.run import ROOT, open_chip

    if open_chip("record_trace") is None:
        return 2
    cfg = small_config(spec.load_json(ROOT / "bench/configs/granite-3-2b.json"))
    engine = sut.build_engine(cfg, weights.make(cfg, 0))
    rng = spec.rng(0, "record")
    harness.warm_up(engine, cfg, sut, rng)
    for i in range(4):
        engine.submit(sut.Request(f"r{i}", rng.integers(0, 512, 20).tolist(), 8))
    stamper = harness._Stamper(engine, {})
    stamper.step()
    log_dir = tempfile.mkdtemp(prefix="bench-record-")
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
        for _ in range(3):
            stamper.step()
        time.sleep(0.001)
    jax.profiler.stop_trace()
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(trace_mod.find_xplane(log_dir), out / "engine_steps.xplane.pb")
    shutil.rmtree(log_dir, ignore_errors=True)
    print(trace_mod.load(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
