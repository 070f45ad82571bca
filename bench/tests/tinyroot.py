"""A throwaway checkout for the CPU tests: its own BENCHMARK.json with
cells on a U-Net small enough to compile in seconds, its own
configuration and traffic files, and links to the real drivers,
metrics, reference and program.  Cells run there with
``run(..., require_tpu=False, cache_dir=None)``; ``register()`` adds the
small model and data set to the program's registries first."""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

MODEL, DATA = "bench-tiny-unet", "bench-tiny"

TINY_CONFIG = {
    "name": "tiny-unet", "source": "test", "program_model": MODEL,
    "reference": "ddpm_unet_ref", "image_size": 8, "in_channels": 3,
    "base_channels": 8, "channel_mults": [1, 2], "num_res_blocks": 1,
    "attn_resolutions": [4], "diffusion_steps": 20,
    "beta_start": 0.0001, "beta_end": 0.02, "prune_ratio": 0.44,
    "lambda0": 0.0001, "lr": 0.0002, "grad_clip": 1.0, "adam_b1": 0.9,
    "adam_b2": 0.999, "adam_eps": 1e-08, "matmul_operands": "float32",
    "check_limits": {"train": {"step_loss_gap": 1e-4,
                               "first_grad_norm_gap": 1e-3,
                               "change_norm_gap": 1e-3,
                               "change_diff_gap": 0.05,
                               "edge_change_norm_gap": 1e-3}},
}

TRAFFIC = {
    "train": {"driver": "train", "preset": "smoke", "engine": "sequential",
              "phase": "sparse",
              "data": {"dataset": DATA, "batch_size": 8},
              "fl": {"num_clients": 2, "participation": 1.0,
                     "sparse_rounds": 50, "cloud_agg_every": 5}},
}
E2E = {"train": "train_img_per_s"}


def register() -> None:
    from repro.configs import ALL_CONFIGS, get_config, register_config
    from repro.data.synthetic import DatasetSpec
    from repro.experiment.data import DATASETS, register_dataset
    if MODEL not in ALL_CONFIGS:
        register_config(MODEL, get_config("ddpm-unet-smoke").replace(
            name=MODEL, image_size=8, base_channels=8, channel_mults=(1, 2),
            num_res_blocks=1, attn_resolutions=(4,), diffusion_steps=20))
    if DATA not in DATASETS:
        register_dataset(DATA, DatasetSpec(DATA, num_classes=4,
                                           image_size=8,
                                           samples_per_class=16))


def make(tmp: str, cells=("train",), config=None,
         extra_metrics=None) -> str:
    """Lay a checkout out under ``tmp``; returns its root.
    ``extra_metrics``: {name: (source, entry)} written as new metric
    files with their BENCHMARK.json entries."""
    bench = os.path.join(tmp, "bench")
    for d in ("configs", "workloads", "metrics"):
        os.makedirs(os.path.join(bench, d), exist_ok=True)
    for d in ("drivers", "lib"):
        os.symlink(os.path.join(BENCH, d), os.path.join(bench, d))
    for f in os.listdir(os.path.join(BENCH, "metrics")):
        if f.endswith(".py"):
            os.symlink(os.path.join(BENCH, "metrics", f),
                       os.path.join(bench, "metrics", f))
    os.symlink(os.path.join(BENCH, "configs", "ddpm_unet_ref.py"),
               os.path.join(bench, "configs", "ddpm_unet_ref.py"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(tmp, "src"))
    with open(os.path.join(bench, "configs", "tiny-unet.json"), "w") as f:
        json.dump(config or TINY_CONFIG, f)
    for c in cells:
        with open(os.path.join(bench, "workloads", c + ".json"), "w") as f:
            json.dump(TRAFFIC[c], f)
    layer = [{"name": "compile_s.train", "unit": "s", "better": "lower",
              "source": "program_counter", "layer": "trainer",
              "moves": "train_img_per_s", "workloads": ["tiny.train"]}] \
        if "train" in cells else []
    for name, (src, entry) in (extra_metrics or {}).items():
        with open(os.path.join(bench, "metrics", name + ".py"), "w") as f:
            f.write(src)
        layer.append(dict(entry, name=name))
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny-unet", "source": "test",
                     "file": "bench/configs/tiny-unet.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "tiny." + c, "config": "tiny-unet",
                       "traffic": c, "chips": 1, "why": "test"}
                      for c in cells],
        "end_to_end": [{"name": E2E[c], "unit": "x", "better": "higher",
                        "bound": 0.1, "source": "host_clock",
                        "workloads": ["tiny." + c]} for c in cells]
        + [{"name": "setup_s", "unit": "s", "better": "lower",
            "bound": 0.25, "source": "host_clock"}],
        "per_layer": layer,
    }
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return tmp


def run_cell(root: str, cell: str, *, seed: int = 2**31 + 7,
             precision: str = "fp32", readings: bool = False) -> dict:
    """One run of ``tiny.<cell>`` on the CPU, its compiled programs kept
    in ``<root>/.jax_cache`` for the next run (see ``compile_cache``)."""
    import run as bench_run
    register()
    args = argparse.Namespace(workload="tiny." + cell, seed=seed,
                              seconds=0.3, trace=0)
    return bench_run.run(args, precision=precision, require_tpu=False,
                         root=root, readings=readings,
                         cache_dir=os.path.join(root, ".jax_cache"))


@contextlib.contextmanager
def compile_cache():
    """Restore JAX's persistent-cache settings after runs that set them,
    so that other tests in the process see the cache as they left it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir", "jax_compilation_cache_max_size",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    try:
        yield
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
