"""Serve a small LM with batched requests + content-addressed prefix cache.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
from repro.launch.serve import main

if __name__ == "__main__":
    import sys

    sys.argv = [sys.argv[0], "--arch", "qwen3_4b", "--smoke"]
    main()
