"""End-to-end driver: train a language model with the distributed HPP
runtime (circular pipeline x data parallel x tensor parallel) on virtual
devices, demonstrating loss convergence and checkpointing.

Default is CPU-sized; ``--full`` trains a ~100M-parameter model for a few
hundred steps (the assignment's reference workload — slow on one CPU core,
exactly the same code on a TPU slice).

    PYTHONPATH=src python examples/train_hpp.py [--full]
"""

import argparse
import os
import tempfile

ap = argparse.ArgumentParser()
ap.add_argument("--full", action="store_true",
                help="~100M params, 200 steps (slow on CPU)")
ap.add_argument("--steps", type=int, default=None)
args = ap.parse_args()

argv = ["--arch", "phi3-mini-3.8b", "--smoke", "--devices", "8",
        "--global-batch", "16", "--seq", "128",
        "--steps", str(args.steps or (200 if args.full else 30)),
        "--log-every", "5",
        "--checkpoint-dir", os.path.join(tempfile.gettempdir(), "repro_ckpt")]
if args.full:
    # ~100M params: 12 layers x d_model 768 on the phi3-mini skeleton
    argv += ["--d-model", "768", "--n-layers", "12", "--seq", "256"]

from repro.launch.train import main  # noqa: E402

final_loss = main(argv)["loss"]
assert final_loss < 6.0, f"loss did not improve: {final_loss}"
print(f"OK: final loss {final_loss:.3f} (started ~ln(vocab)=6.2+)")
