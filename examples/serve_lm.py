"""Batched serving driver: prefill a batch of prompts, then greedy-decode
with per-kind KV caches (dense / ring / recurrent states).

    PYTHONPATH=src python examples/serve_lm.py --arch recurrentgemma-2b-smoke
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.train.serve_step import greedy_generate


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b-smoke")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=24)
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab)}
    if cfg.family == "vlm":
        batch["image_embeds"] = jnp.zeros(
            (args.batch, cfg.n_image_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "audio":
        batch["audio_embeds"] = jnp.zeros(
            (args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)

    t0 = time.perf_counter()
    out = greedy_generate(model, params, batch, steps=args.steps)
    dt = time.perf_counter() - t0
    toks = args.batch * args.steps
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"steps={args.steps}")
    print(f"generated:\n{out}")
    print(f"{toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s incl. compile)")


if __name__ == "__main__":
    main()
