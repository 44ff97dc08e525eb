"""End-to-end serving example: batched prefill → decode with the split
(prefix + hot-ring) KV cache, TALP-monitored, for a hybrid SSM+attention
architecture (zamba2 family).

Run:  PYTHONPATH=src python examples/serve_lm.py [--gen-len 24]
"""

import argparse

from repro.configs import smoke_config
from repro.launch.serve import serve


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="zamba2-2.7b")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=12)
    args = ap.parse_args()

    cfg = smoke_config(args.arch)
    tokens, talp, *_ = serve(cfg, requests=args.requests,
                             prompt_len=args.prompt_len,
                             gen_len=args.gen_len)
    print(f"generated token matrix: {tokens.shape} "
          f"(requests × new tokens)")
    decode = talp.regions["decode"]
    print(f"decode-region Device Offload Eff.: "
          f"{decode.host.device_offload_efficiency:.3f}")


if __name__ == "__main__":
    main()
