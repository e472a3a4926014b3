"""Serve an assigned architecture with batched requests: prefill + greedy
decode through the KV/state-cache path (reduced config on CPU).

  PYTHONPATH=src python examples/serve_arch.py --arch jamba-1.5-large-398b
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.serve import build_parser, main as serve_main
from repro.utils.cache import use_compilation_cache

if __name__ == "__main__":
    use_compilation_cache()
    # same parser as the driver — only the defaults differ, so new
    # launch/serve.py flags are picked up here without duplication
    parser = build_parser()
    parser.set_defaults(arch="jamba-1.5-large-398b", batch=2, prompt_len=12,
                        tokens=8)
    serve_main(sys.argv[1:], parser=parser)
