"""Batched serving driver: prefill a prompt batch, then greedy-decode.

CPU-scale:  python -m repro.launch.serve --arch qwen3-0.6b --smoke \
                --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, plan_for_mesh, smoke_of
from repro.launch.mesh import make_local_mesh
from repro.models import decode_step, param_defs, prefill
from repro.models.layers import ParamDef
from repro.train.trainer import init_params_sharded

IS_DEF = lambda t: isinstance(t, ParamDef)  # noqa: E731


def serve(arch, mesh, plan, *, batch: int, prompt_len: int, gen: int,
          seed: int = 0, params=None):
    pdefs = param_defs(arch)
    specs = jax.tree.map(lambda d: plan.spec(d.dims, d.shape), pdefs,
                         is_leaf=IS_DEF)
    if params is None:
        params = init_params_sharded(pdefs, mesh, specs, seed)
    rng = np.random.default_rng(seed)
    batch_in = {"tokens": jnp.asarray(
        rng.integers(0, arch.vocab_size, (batch, prompt_len)), jnp.int32)}
    if arch.enc_dec:
        batch_in["enc_embeds"] = jnp.asarray(
            rng.normal(0, 1, (batch, arch.enc_len, arch.d_model)),
            jnp.float32)
    if arch.n_patches:
        batch_in["patch_embeds"] = jnp.asarray(
            rng.normal(0, 0.02, (batch, arch.n_patches, arch.d_model)),
            jnp.float32)
        batch_in["pos3"] = jnp.broadcast_to(
            jnp.arange(prompt_len, dtype=jnp.int32)[None, None],
            (3, batch, prompt_len))

    prefill_fn = jax.jit(lambda p, b: prefill(p, b, arch, plan, prompt_len))
    step_fn = jax.jit(lambda p, c, t: decode_step(p, c, t, arch, plan))

    with jax.set_mesh(mesh):
        t0 = time.time()
        cache, logits = prefill_fn(params, batch_in)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        jax.block_until_ready(tok)
        t_prefill = time.time() - t0
        out = [tok]
        t0 = time.time()
        for _ in range(gen - 1):
            cache, logits = step_fn(params, cache, tok)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            out.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.time() - t0
    tokens = jnp.concatenate(out, axis=1)
    return tokens, dict(
        prefill_s=t_prefill, decode_s=t_decode,
        tok_per_s=batch * (gen - 1) / max(t_decode, 1e-9))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()
    arch = get_arch(args.arch)
    if args.smoke:
        arch = smoke_of(arch)
    mesh = make_local_mesh()
    plan = plan_for_mesh(mesh)
    tokens, stats = serve(arch, mesh, plan, batch=args.batch,
                          prompt_len=args.prompt_len, gen=args.gen)
    print("generated shape:", tokens.shape)
    print({k: round(v, 4) for k, v in stats.items()})


if __name__ == "__main__":
    main()
