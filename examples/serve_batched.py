"""Batched serving example: prefill + greedy decode on any registered
architecture (smoke-sized), including the enc-dec (whisper) and hybrid
(recurrentgemma) cache paths.

    PYTHONPATH=src python examples/serve_batched.py --arch xlstm-1.3b
"""
import argparse

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_smoke, list_archs
from repro.launch.serve import serve


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="recurrentgemma-2b",
                    choices=list_archs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    cfg = get_smoke(args.arch)
    print(f"serving {cfg.name} (smoke config, batch={args.batch})")
    toks = serve(cfg, batch=args.batch, prompt_len=args.prompt_len,
                 gen=args.gen)
    print("generated ids:")
    print(np.asarray(toks))


if __name__ == "__main__":
    main()
