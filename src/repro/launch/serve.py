"""Serving launcher: batched prefill + decode with KV cache, plus the
Ganesha-style cache-invalidation loop over LCAP (paper §IV-C-1).

Replicas prefill prompts into a KV/page cache keyed by (prompt-id,
version).  When a prompt's backing object changes (simulated admin
write), the owning replica emits CL_EVICT; every other replica is an
EPHEMERAL changelog reader and drops its stale entry — exactly the
paper's loose metadata-cache invalidation.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --smoke
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen-len", type=int, default=8)
    ap.add_argument("--replicas", type=int, default=2)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from .. import configs as C
    from ..core.proxy import LcapProxy
    from ..models import transformer as T
    from ..track import ActivityTracker, CacheInvalidator
    from ..runtime.steps import build_decode_step, build_prefill_step
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = C.get_smoke(args.arch) if args.smoke else C.get_config(args.arch)
    params = T.init_params(cfg, seed=0)
    B, P, G = args.batch, args.prompt_len, args.gen_len
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, P)), jnp.int32)
    batch = {"tokens": tokens}
    if cfg.is_encoder_decoder:
        batch["frames"] = jnp.asarray(
            rng.randn(B, cfg.n_frames, cfg.d_model), jnp.float32)
    if cfg.n_image_patches:
        batch["image_embeds"] = jnp.asarray(
            rng.randn(B, cfg.n_image_patches, cfg.d_model), jnp.float32)

    prefill = jax.jit(build_prefill_step(cfg, max_seq=P + G,
                                         attn_impl="naive"))
    decode = jax.jit(build_decode_step(cfg), donate_argnums=(1,))

    logits, cache = prefill(params, batch)
    out_tokens = [jnp.argmax(logits, -1)]
    for i in range(G - 1):
        pos = jnp.full((B,), P + i, jnp.int32)
        logits, cache = decode(params, cache, out_tokens[-1][:, None], pos)
        out_tokens.append(jnp.argmax(logits, -1))
    gen = jnp.stack(out_tokens, 1)

    # --- LCAP cache invalidation across replicas (paper §IV-C-1) ---------
    owner = ActivityTracker(run_id=1, host_id=0, jobid="serve-owner")
    proxy = LcapProxy({"host0": owner.llog})
    page_caches = [{(pid, 1): f"kv-page-{pid}" for pid in range(B)}
                   for _ in range(args.replicas)]
    invalidators = [CacheInvalidator(proxy, pc) for pc in page_caches]
    owner.evict(2, 1, reason="prompt-updated")      # object 2 changed
    proxy.pump()
    for inv in invalidators:
        inv.poll()

    print(json.dumps({
        "arch": cfg.arch_id,
        "generated_shape": list(gen.shape),
        "generated_finite": bool(jnp.all(gen >= 0)),
        "evicted_per_replica": [inv.invalidated for inv in invalidators],
        "remaining_pages": [len(pc) for pc in page_caches],
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
