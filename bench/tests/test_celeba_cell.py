"""What the CelebA cell adds, at the small size on the CPU: a program
whose local step runs under a rematerialization policy
(``remat_policy``), clients whose data the batch size does not divide
(each epoch drops its last partial batch), and the driver that reads
such clients' whole label counts for the edge check
(``train_partial_epoch``).  A sound run is correct against the
reference, and ``step_temp_gib.train`` reads the compiled step's
temporary bytes after the run."""
import json
import os

import tinyroot
from lib import harness

MODEL = "bench-tiny-unet-remat"


def test_remat_run_with_partial_batches_is_correct(tmp_path):
    from repro.configs import ALL_CONFIGS, get_config, register_config
    tinyroot.register()
    if MODEL not in ALL_CONFIGS:
        register_config(MODEL, get_config(tinyroot.MODEL).replace(
            name=MODEL, remat_policy="dots_with_no_batch_dims_saveable"))
    root = tinyroot.make(str(tmp_path), config=dict(tinyroot.TINY_CONFIG,
                                                    program_model=MODEL))
    traffic = tinyroot.TRAFFIC["train"]
    with open(os.path.join(root, "bench", "workloads", "train.json"),
              "w") as f:       # 32 images a client: 5 steps of 6, 2 left
        json.dump(dict(traffic, driver="train_partial_epoch",
                       data=dict(traffic["data"], batch_size=6)), f)
    with tinyroot.compile_cache():
        out = tinyroot.run_cell(root, "train")
    assert out["correct"], out["checks"]
    assert out["checks"]["edge_change_norm_gap"]["value"] <= 1e-3
    cell = harness.find_cell("tiny.train", root)
    read = harness.load_metric(cell, "step_temp_gib.train").read
    assert read(harness.Context(cell=cell, seed=1), None) > 0
