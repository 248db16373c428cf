"""LANGUAGE-MODEL serving example: batched prefill + decode for a
decoder-only transformer (thin wrapper over the LM demo driver,
repro/launch/serve.py).

Not the p-bit sampling service — that is `python -m repro.serve`
(see docs/serving.md and examples/serve_pbit.py).

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import sys

from repro.launch.serve import main

if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    sys.argv = [sys.argv[0], "--arch", "gemma2-2b", "--reduced",
                "--batch", "4", "--prompt-len", "32", "--gen", "32",
                *sys.argv[1:]]
    main()
