"""End-to-end LM training driver example (deliverable b): train a ~100M
reduced Qwen3 variant for a few hundred steps on the synthetic pipeline.

  PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.train import main as train_main
from repro.utils.cache import use_compilation_cache

if __name__ == "__main__":
    use_compilation_cache()
    args = sys.argv[1:] or ["--arch", "qwen3-4b", "--steps", "200",
                            "--batch", "8", "--seq", "128", "--lr", "3e-3",
                            "--ckpt", tempfile.mkdtemp(prefix="train_lm_")]
    train_main(args)
