"""Record ``data/qat_scoped.xplane.pb`` on one TPU chip: two layers of
smollm-135m at full width, two QAT steps of 2 x 128 tokens through
``Trainer.fit`` inside one ``bench.qat.step`` host span, with the profiler's
Python tracer off.

    python3 perfbench/tests/record_trace.py <output .xplane.pb>
"""
import dataclasses
import glob
import os
import shutil
import sys
import tempfile

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def main(out: str) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch.specs import make_acfg
    from repro.models.transformer import init_params, loss_fn
    from repro.optim.adamw import AdamW, cosine_schedule
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = dataclasses.replace(get_config("smollm-135m"), n_layers=2,
                              dtype="float32")
    acfg = make_acfg("mul8s_1L2H:lut", approx_bwd=True)
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(0))
    opt = AdamW(lr=cosine_schedule(3e-4, 100, 100), weight_decay=0.01)
    tr = Trainer(lambda p, b: loss_fn(p, b["tokens"], b["labels"], cfg, acfg),
                 opt, TrainerConfig(log_every=1))
    rng = np.random.default_rng(0)

    def batches():
        while True:
            t = rng.integers(0, cfg.vocab_size, (2, 129)).astype(np.int32)
            yield {"tokens": t[:, :-1], "labels": t[:, 1:]}

    feed = batches()
    params, state = tr.fit(params, opt.init(params), feed, 1)   # compiles
    trace_dir = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.qat.step"):
        tr.fit(params, state, feed, 2)
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    shutil.copy(path, out)
    shutil.rmtree(trace_dir)


if __name__ == "__main__":
    main(sys.argv[1])
