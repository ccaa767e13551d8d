"""Multi-tenant serving throughput: scan decode, adapters, slot batching.

Three measurements over the smoke transformer (CPU-sized; the same step
functions lower to the production mesh):

  scan-vs-eager   the fused ``lax.scan`` decode against the eager
                  per-token loop at B=4 / new_tokens=64 — the per-token
                  dispatch overhead the scan amortizes into one program.
                  Greedy outputs must match bit-for-bit (decode_parity).
  adapter sweep   tokens/s of the heterogeneous-adapter batch (every row
                  its own ``(basis, R̃)`` via the batched kernel) as the
                  tenant count G sweeps 1→256 at B=8, against (a) the
                  single-adapter table and (b) merged-weight serving
                  (adapter materialized into the dense weights — the
                  per-tenant-copy baseline that cannot batch tenants).
  continuous      SlotServer throughput serving 3x-oversubscribed
                  requests through a fixed slot batch, with per-request
                  greedy parity against straight ``generate``.

Timing hygiene: every clock read is fenced with ``block_until_ready`` on
the stage's outputs (prefill and decode separately — async dispatch would
otherwise charge prefill compute to the decode clock), and the compile
iteration is excluded (best-of-``iters`` steady-state).

Acceptance keys (gated by ``scripts/ci.sh --serve-smoke``):
  decode_parity            scan ≡ eager greedy tokens (exact)
  scan_speedup_b4_n64      eager decode s / scan decode s, must be ≥ 1
  hetero_tput_ratio_g16_b8 G=16 hetero tokens/s / G=1 tokens/s, ≥ 0.8
  continuous_parity        SlotServer ≡ straight generate per request
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke_variant
from repro.core import projector as proj
from repro.core.fed import merge_dense, split_trainable
from repro.launch import adapters as adapters_lib
from repro.launch import serve
from repro.models import model as M

from .common import dump_json, emit

ARCH = "qwen1.5-0.5b"
ADAPTER_SWEEP = (1, 4, 16, 64, 256)
HETERO_GATE_G = 16


def _timed_generate(mode, params, cfg, prompts, new_tokens, cache_len,
                    ids=None, iters=2):
    """Best-of-``iters`` fenced (prefill_s, decode_s) for one serving path;
    the first (compile) iteration is excluded from the clocks."""
    pre = serve._prefill_fn(cfg)
    key = jax.random.PRNGKey(0)
    dec = (serve._scan_decode_fn(cfg, new_tokens - 1, 0.0)
           if mode == "scan" else None)
    step = serve._eager_step_fn(cfg) if mode == "eager" else None
    best_pf = best_dc = float("inf")
    out = None
    for it in range(iters + 1):
        state = M.init_decode_state(cfg, prompts.shape[0], cache_len)
        jax.block_until_ready((params, prompts))
        t0 = time.perf_counter()
        logits, state = pre(params, prompts, state, ids)
        jax.block_until_ready(logits)
        t1 = time.perf_counter()
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if mode == "scan":
            toks, _ = dec(params, tok, state, key, ids)
            jax.block_until_ready(toks)
            out = jnp.concatenate([tok[:, None], toks], axis=1)
        else:
            outl = [tok]
            for _ in range(new_tokens - 1):
                logits, state = step(params, tok, state, ids)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                outl.append(tok)
            jax.block_until_ready(tok)
            out = jnp.stack(outl, axis=1)
        t2 = time.perf_counter()
        if it > 0:
            best_pf = min(best_pf, t1 - t0)
            best_dc = min(best_dc, t2 - t1)
    return out, best_pf, best_dc


def _merge_adapter(params, target_fn, basis, rt, scale=1.0):
    """Materialize one adapter into the dense weights — the per-tenant-copy
    serving baseline (no factored leaves, no batched tenants)."""
    trainable, frozen = split_trainable(params, target_fn)

    def lift(w, b, r):
        w32 = w.astype(jnp.float32)
        if proj.proj_side(w.shape) == proj.RIGHT:
            d = jnp.einsum("...mr,...nr->...mn", jnp.asarray(r),
                           jnp.asarray(b))
        else:
            d = jnp.einsum("...mr,...rn->...mn", jnp.asarray(b),
                           jnp.asarray(r))
        return (scale * w32 + d).astype(w.dtype)

    lifted = jax.tree_util.tree_map(lift, trainable, basis, rt)
    return merge_dense(frozen, lifted)


def bench_scan_vs_eager(cfg, params, *, batch=4, prompt_len=16,
                        new_tokens=64):
    cache = prompt_len + new_tokens
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (batch, prompt_len), 0, cfg.vocab_size)
    out_e, pf_e, dc_e = _timed_generate("eager", params, cfg, prompts,
                                        new_tokens, cache)
    out_s, pf_s, dc_s = _timed_generate("scan", params, cfg, prompts,
                                        new_tokens, cache)
    parity = bool(jnp.array_equal(out_e, out_s))
    rows = []
    for path, pf, dc in (("eager", pf_e, dc_e), ("scan", pf_s, dc_s)):
        rows.append({"section": "scan_vs_eager", "path": path,
                     "batch": batch, "prompt_len": prompt_len,
                     "new_tokens": new_tokens,
                     "prefill_s": pf, "decode_s": dc,
                     "prefill_tok_s": batch * prompt_len / pf,
                     "decode_tok_s": batch * new_tokens / dc})
    return rows, {"decode_parity": parity,
                  "scan_speedup_b4_n64": dc_e / dc_s}


def bench_adapter_sweep(cfg, params, *, batch=8, prompt_len=16,
                        new_tokens=32, rank=4, sweep=ADAPTER_SWEEP):
    cache = prompt_len + new_tokens
    prompts = jax.random.randint(jax.random.PRNGKey(2),
                                 (batch, prompt_len), 0, cfg.vocab_size)
    tf = adapters_lib.serving_target_fn(cfg)
    rng = np.random.default_rng(0)
    g_max = max(sweep)
    store = adapters_lib.AdapterStore(params, tf, g_max, rank)
    factors = []
    for g in range(g_max):
        basis, rt = store.random_factors(rng)
        store.put(g, rt, basis)
        factors.append((basis, rt))

    rows, tok_s = [], {}
    for g in sweep:
        served = store.wrap(params, ids=np.arange(g))
        ids = jnp.arange(batch, dtype=jnp.int32) % g
        _, pf, dc = _timed_generate("scan", served, cfg, prompts,
                                    new_tokens, cache, ids=ids)
        tok_s[g] = batch * new_tokens / dc
        rows.append({"section": "adapter_sweep", "adapters": g,
                     "batch": batch, "new_tokens": new_tokens,
                     "prefill_s": pf, "decode_s": dc,
                     "decode_tok_s": tok_s[g]})

    # merged-weight baseline: one tenant baked into dense weights — what a
    # per-tenant weight copy serves (the whole batch must share it).
    merged = _merge_adapter(params, tf, *factors[0])
    _, pf_m, dc_m = _timed_generate("scan", merged, cfg, prompts,
                                    new_tokens, cache)
    merged_tok_s = batch * new_tokens / dc_m
    rows.append({"section": "adapter_sweep", "adapters": "merged-1",
                 "batch": batch, "new_tokens": new_tokens,
                 "prefill_s": pf_m, "decode_s": dc_m,
                 "decode_tok_s": merged_tok_s})
    gate_g = HETERO_GATE_G if HETERO_GATE_G in tok_s else max(tok_s)
    acc = {"adapter_sweep_tok_s": {str(g): tok_s[g] for g in tok_s},
           "merged_tok_s": merged_tok_s,
           "hetero_gate_adapters": gate_g,
           "hetero_tput_ratio_g16_b8": tok_s[gate_g] / tok_s[min(tok_s)],
           "hetero_vs_merged_g16": tok_s[gate_g] / merged_tok_s}
    return rows, acc


def bench_continuous(cfg, params, *, slots=4, segment=8, prompt_len=12,
                     new_tokens=24, requests=12):
    cache = prompt_len + new_tokens
    rng = np.random.default_rng(3)
    reqs = [serve.Request(rid=i,
                          prompt=rng.integers(0, cfg.vocab_size, prompt_len),
                          max_new=new_tokens)
            for i in range(requests)]
    # warmup: compile prefill/insert/segment on a throwaway server
    serve.SlotServer(params, cfg, slots=slots, cache_len=cache,
                     segment=segment).run([serve.Request(
                         rid=-1, prompt=reqs[0].prompt, max_new=2)])
    server = serve.SlotServer(params, cfg, slots=slots, cache_len=cache,
                              segment=segment)
    out = server.run(reqs)
    stats = out["stats"]
    parity = True
    for r in reqs:
        ref = serve.generate(params, cfg,
                             jnp.asarray(r.prompt, jnp.int32)[None],
                             new_tokens, cache)
        if out["outputs"][r.rid] != ref[0, -new_tokens:].tolist():
            parity = False
    row = {"section": "continuous", "slots": slots, "segment": segment,
           "requests": requests, "new_tokens": new_tokens, **stats}
    acc = {"continuous_parity": parity,
           "continuous_decode_tok_s": stats["decode_tok_s"],
           "continuous_segments": stats["segments"]}
    return [row], acc


def main(out_path="BENCH_serve.json", smoke=False):
    cfg = smoke_variant(get_config(ARCH))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    sweep = (1, 4, 16) if smoke else ADAPTER_SWEEP

    rows, acc = [], {}
    r, a = bench_scan_vs_eager(cfg, params)
    rows += r
    acc.update(a)
    r, a = bench_adapter_sweep(cfg, params, sweep=sweep)
    rows += r
    acc.update(a)
    r, a = bench_continuous(cfg, params,
                            requests=8 if smoke else 12)
    rows += r
    acc.update(a)

    result = {"arch": cfg.name, "rows": rows, "acceptance": acc}
    dump_json(out_path, result)
    emit("serve/scan_speedup_b4_n64", 0.0,
         f"x{acc['scan_speedup_b4_n64']:.2f};parity="
         f"{acc['decode_parity']}")
    emit("serve/hetero_ratio_g16_b8", 0.0,
         f"x{acc['hetero_tput_ratio_g16_b8']:.2f};"
         f"vs_merged=x{acc['hetero_vs_merged_g16']:.2f}")
    emit("serve/continuous_decode_tok_s",
         0.0, f"{acc['continuous_decode_tok_s']:.1f};parity="
         f"{acc['continuous_parity']}")
    return result


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small adapter sweep for CI perf tracking")
    args = ap.parse_args()
    main(out_path=args.out, smoke=args.smoke)
