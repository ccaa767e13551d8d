"""Multi-tenant low-rank serving: fused scan decode + continuous batching.

Three serving paths over the same model, slowest to fastest:

- :func:`generate`       eager per-token loop — one jitted ``decode_step``
                         dispatch per token. Kept as the parity oracle
                         (greedy scan decode must match it bit-for-bit).
- :func:`generate_scan`  the whole decode loop as ONE jitted ``lax.scan``:
                         no per-token Python dispatch, decode state donated
                         so KV ring buffers update in place, sampling keys
                         derived in-scan with ``jax.random.fold_in``.
- :class:`SlotServer`    continuous batching on top of the scan: requests
                         occupy slots of a fixed decode batch, finished
                         sequences retire mid-stream via in-scan EOS/length
                         masks, and queued requests are admitted into freed
                         slots between scan segments (per-request prefill +
                         jitted in-mesh slot insert).

Per-row heterogeneous adapters ride along on all three paths: pass
``adapters`` (B,) int ids and params whose target leaves are
``MultiAdapterDelta`` tables (built by :mod:`repro.launch.adapters`) — each
decode row then applies its own factored ``(basis, R̃)`` delta over one
shared base GEMM, so one compiled batch serves many tenants.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --smoke \
      --batch 4 --prompt-len 32 --new-tokens 16 --mode scan
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import time
from typing import Any, Dict, List

import numpy as np

import jax
import jax.numpy as jnp

from ..configs import get_config, smoke_variant
from ..models import layers
from ..models import model as model_lib
from .cache import use_compile_cache

PAD_ID = 0   # emitted by retired slots inside a segment; never surfaced


def _env_hygiene() -> None:
    """Launcher hygiene, applied BEFORE jax touches the backend (mirrors
    benchmarks/run.py and the shell block in scripts/ci.sh): tcmalloc
    preload can't be done from in-process (LD_PRELOAD is read at exec), but
    the allocator threshold, C++ log level, and XLA host-device plumbing
    are env-var driven and honored at first backend initialization — which
    happens at the first jax *operation*, after this runs."""
    os.environ.setdefault("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD",
                          "60000000000")
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")
    flags = []
    host_devices = os.environ.get("REPRO_HOST_DEVICES")
    if host_devices:
        flags.append(f"--xla_force_host_platform_device_count={host_devices}")
    # Opt-in only: rejected by CPU builds of XLA (unknown-flag error).
    if os.environ.get("REPRO_STEP_MARKERS") == "1":
        flags.append("--xla_step_marker_location=1")
    if flags:
        prev = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (prev + " " + " ".join(flags)).strip()


def _sample(logits, key, temperature):
    """Greedy argmax when temperature <= 0 (key unused), else categorical."""
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)


# --------------------------------------------------------------------------
# Cached jitted programs. ArchConfig is a frozen (hashable) dataclass, so it
# keys lru_cache directly; jit's own cache handles shape polymorphism under
# each entry. ``ids`` is always an argument (None for single-tenant params —
# a leafless pytree, so it costs nothing and avoids a second trace).
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _prefill_fn(cfg):
    def run(params, prompt, state, ids):
        with layers.adapter_ids(ids):
            return model_lib.prefill(params, cfg, prompt, state)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _eager_step_fn(cfg):
    def run(params, tok, state, ids):
        with layers.adapter_ids(ids):
            return model_lib.decode_step(params, cfg, tok, state)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _scan_decode_fn(cfg, steps: int, temperature: float):
    """The fused decode loop: ``steps`` tokens after the prefill-sampled
    one, as a single device program. Returns ``(tokens (B, steps), final
    state)``; the state is donated and returned, so the KV ring buffers
    alias in place instead of being copied into the loop."""
    def run(params, tok0, state, key, ids):
        def body(carry, i):
            tok, st = carry
            with layers.adapter_ids(ids):
                logits, st = model_lib.decode_step(params, cfg, tok, st)
            nxt = _sample(logits, jax.random.fold_in(key, i), temperature)
            return (nxt, st), nxt
        (_, state), toks = jax.lax.scan(body, (tok0, state),
                                        jnp.arange(steps))
        return jnp.moveaxis(toks, 0, 1), state     # (B, steps)
    return jax.jit(run, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _insert_fn(cfg):
    """In-mesh slot insert: write one prefilled request's cache rows, its
    absolute position, and its first token into slot ``slot`` of the live
    batched decode state. Layer-state leaves are stacked (nb, B, ...), so
    the slot axis is 1."""
    def run(state, tok, slot, sub_state, sub_tok):
        new_layers = jax.tree_util.tree_map(
            lambda big, small: jax.lax.dynamic_update_slice_in_dim(
                big, small.astype(big.dtype), slot, axis=1),
            state.layers, sub_state.layers)
        new_t = state.t.at[slot].set(sub_state.t)
        return (model_lib.DecodeState(t=new_t, layers=new_layers),
                tok.at[slot].set(sub_tok[0]))
    return jax.jit(run, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _segment_fn(cfg, segment: int, temperature: float, eos_id: int):
    """One continuous-batching segment: ``segment`` fused decode steps with
    in-scan retirement — a row that emits ``eos_id`` or exhausts its budget
    goes inactive and emits PAD_ID for the rest of the segment (its state
    keeps advancing harmlessly; admission overwrites the whole slot)."""
    def run(params, tok, state, active, remaining, ids, key, base):
        def body(carry, i):
            tok, st, act, rem = carry
            with layers.adapter_ids(ids):
                logits, st = model_lib.decode_step(params, cfg, tok, st)
            nxt = _sample(logits, jax.random.fold_in(key, base + i),
                          temperature)
            nxt = jnp.where(act, nxt, PAD_ID)
            rem = jnp.where(act, rem - 1, rem)
            act = act & (rem > 0)
            if eos_id >= 0:
                act = act & (nxt != eos_id)
            return (nxt, st, act, rem), nxt
        (tok, state, active, remaining), toks = jax.lax.scan(
            body, (tok, state, active, remaining), jnp.arange(segment))
        return tok, state, active, remaining, jnp.moveaxis(toks, 0, 1)
    return jax.jit(run, donate_argnums=(1, 2))


# --------------------------------------------------------------------------
# Whole-sequence drivers
# --------------------------------------------------------------------------

def generate(params, cfg, prompts, new_tokens: int, cache_len: int,
             temperature: float = 0.0, key=None, adapters=None):
    """prompts (B, L) -> (B, L + new_tokens). Greedy when temperature == 0.

    The eager per-token loop — the parity oracle for :func:`generate_scan`.
    ``adapters`` (B,) int ids select each row's factor set when params
    carry ``MultiAdapterDelta`` leaves.
    """
    b = prompts.shape[0]
    ids = None if adapters is None else jnp.asarray(adapters, jnp.int32)
    state = model_lib.init_decode_state(cfg, b, cache_len)
    logits, state = _prefill_fn(cfg)(params, prompts, state, ids)

    key = key if key is not None else jax.random.PRNGKey(0)
    tok = _sample(logits, key, temperature)
    out = [tok]

    step = _eager_step_fn(cfg)
    for _ in range(new_tokens - 1):
        key, sub = jax.random.split(key)
        logits, state = step(params, tok, state, ids)
        tok = _sample(logits, sub, temperature)
        out.append(tok)
    return jnp.concatenate([prompts, jnp.stack(out, axis=1)], axis=1)


def generate_scan(params, cfg, prompts, new_tokens: int, cache_len: int,
                  temperature: float = 0.0, key=None, adapters=None):
    """Fused twin of :func:`generate`: the decode loop is ONE jitted
    ``lax.scan`` dispatch. Greedy output is bit-identical to the eager
    oracle; at temperature > 0 both are valid draws from the same model
    but use different key chains (in-scan ``fold_in`` here, sequential
    splits there)."""
    b = prompts.shape[0]
    ids = None if adapters is None else jnp.asarray(adapters, jnp.int32)
    state = model_lib.init_decode_state(cfg, b, cache_len)
    logits, state = _prefill_fn(cfg)(params, prompts, state, ids)
    key = key if key is not None else jax.random.PRNGKey(0)
    tok0 = _sample(logits, key, temperature)
    if new_tokens <= 1:
        return jnp.concatenate([prompts, tok0[:, None]], axis=1)
    toks, _ = _scan_decode_fn(cfg, new_tokens - 1, float(temperature))(
        params, tok0, state, key, ids)
    return jnp.concatenate([prompts, tok0[:, None], toks], axis=1)


# --------------------------------------------------------------------------
# Continuous batching
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    """One serving request: ``prompt`` (L,) int tokens, decode budget
    ``max_new``, and the adapter id its rows should apply."""
    rid: int
    prompt: Any
    max_new: int
    adapter: int = 0


class SlotServer:
    """Slot-based continuous batching over the fused segment scan.

    A fixed decode batch of ``slots`` rows runs ``segment``-step fused
    scans. Rows retire mid-segment (EOS or budget) via in-scan masks;
    between segments the host drains finished slots and admits queued
    requests into the free ones — per-request prefill, then a jitted
    in-mesh insert of the slot's cache rows, position, and first token.
    Nothing about an admit recompiles: the segment program is fixed-shape.
    """

    def __init__(self, params, cfg, *, slots: int, cache_len: int,
                 segment: int = 8, eos_id: int = -1,
                 temperature: float = 0.0, seed: int = 0):
        self.params, self.cfg = params, cfg
        self.slots = int(slots)
        self.cache_len = int(cache_len)
        self.segment = int(segment)
        self.eos_id = int(eos_id)          # -1 = no EOS, budget-only
        self.temperature = float(temperature)
        self.key = jax.random.PRNGKey(seed)
        self.state = model_lib.init_decode_state(cfg, self.slots, cache_len,
                                                 per_slot=True)
        self.tok = jnp.zeros((self.slots,), jnp.int32)
        self.ids = jnp.zeros((self.slots,), jnp.int32)
        # Canonicalize the carry dtypes to decode_step's fixed point: some
        # recurrent-state leaves (e.g. RWKV shift buffers initialized in
        # the param dtype) are promoted to fp32 by the step — the segment
        # scan requires carry-in == carry-out types.
        with layers.adapter_ids(self.ids):
            spec = jax.eval_shape(
                lambda p, t, s: model_lib.decode_step(p, cfg, t, s)[1],
                params, self.tok, self.state)
        self.state = jax.tree_util.tree_map(
            lambda x, sp: x.astype(sp.dtype), self.state, spec)
        self.active = np.zeros(self.slots, bool)
        self.remaining = np.zeros(self.slots, np.int32)
        self.rid = np.full(self.slots, -1, np.int64)
        self.queue: List[Request] = []
        self.outputs: Dict[int, List[int]] = {}
        self._step_base = 0
        self.stats = {"prefill_s": 0.0, "decode_s": 0.0,
                      "prefill_tokens": 0, "decode_tokens": 0,
                      "segments": 0, "admitted": 0}

    def submit(self, request: Request) -> None:
        self.queue.append(request)

    def _admit(self) -> None:
        """Fill free slots from the queue (per-request prefill + insert)."""
        for slot in range(self.slots):
            if not self.queue:
                return
            if self.active[slot]:
                continue
            req = self.queue.pop(0)
            prompt = jnp.asarray(req.prompt, jnp.int32)[None]
            sub_state = model_lib.init_decode_state(self.cfg, 1,
                                                    self.cache_len)
            sub_ids = jnp.full((1,), req.adapter, jnp.int32)
            t0 = time.perf_counter()
            logits, sub_state = _prefill_fn(self.cfg)(
                self.params, prompt, sub_state, sub_ids)
            self.key, sub = jax.random.split(self.key)
            tok1 = _sample(logits, sub, self.temperature)
            jax.block_until_ready(tok1)
            self.stats["prefill_s"] += time.perf_counter() - t0
            self.stats["prefill_tokens"] += int(prompt.shape[1])
            self.state, self.tok = _insert_fn(self.cfg)(
                self.state, self.tok, jnp.asarray(slot, jnp.int32),
                sub_state, tok1)
            self.ids = self.ids.at[slot].set(req.adapter)
            first = int(tok1[0])
            self.outputs[req.rid] = [first]
            done = (req.max_new <= 1 or
                    (self.eos_id >= 0 and first == self.eos_id))
            self.rid[slot] = -1 if done else req.rid
            self.active[slot] = not done
            self.remaining[slot] = max(req.max_new - 1, 0)
            self.stats["admitted"] += 1

    def _run_segment(self) -> None:
        """One fused segment over the live batch; drain outputs after."""
        seg = _segment_fn(self.cfg, self.segment, self.temperature,
                          self.eos_id)
        act_before = self.active.copy()
        rem_before = self.remaining.copy()
        rid_before = self.rid.copy()
        t0 = time.perf_counter()
        self.tok, self.state, act, rem, toks = seg(
            self.params, self.tok, self.state,
            jnp.asarray(self.active), jnp.asarray(self.remaining),
            self.ids, self.key, jnp.asarray(self._step_base, jnp.int32))
        jax.block_until_ready(toks)
        self.stats["decode_s"] += time.perf_counter() - t0
        self.stats["segments"] += 1
        self._step_base += self.segment
        toks_np = np.asarray(toks)
        self.active = np.array(act)            # copies: host mirrors stay
        self.remaining = np.array(rem, np.int32)   # writable for _admit
        for slot in np.nonzero(act_before)[0]:
            take = min(self.segment, int(rem_before[slot]))
            for t in toks_np[slot, :take]:
                self.outputs[int(rid_before[slot])].append(int(t))
                self.stats["decode_tokens"] += 1
                if self.eos_id >= 0 and int(t) == self.eos_id:
                    break
            if not self.active[slot]:
                self.rid[slot] = -1            # retired: slot is free

    def run(self, requests=()) -> Dict[str, Any]:
        """Serve ``requests`` (plus anything already queued) to completion.

        Returns ``{"outputs": {rid: [new tokens...]}, "stats": {...}}`` —
        outputs include the prefill-sampled first token, truncated at EOS.
        """
        for r in requests:
            self.submit(r)
        while self.queue or self.active.any():
            self._admit()
            if self.active.any():
                self._run_segment()
        return {"outputs": self.outputs, "stats": self.stat_summary()}

    def stat_summary(self) -> Dict[str, Any]:
        s = dict(self.stats)
        s["prefill_tok_s"] = (s["prefill_tokens"] / s["prefill_s"]
                              if s["prefill_s"] > 0 else 0.0)
        s["decode_tok_s"] = (s["decode_tokens"] / s["decode_s"]
                             if s["decode_s"] > 0 else 0.0)
        return s


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def main(argv=None):
    """Serve one seeded batch (or request stream) and print its summary.
    Returns the summary plus ``outputs``: every request's generated token
    ids (prompt excluded)."""
    _env_hygiene()
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-1.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", choices=("eager", "scan", "continuous"),
                    default="scan")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode batch (slot count in continuous mode)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=0,
                    help="KV slots (0 = prompt+new)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--adapters", type=int, default=0,
                    help="G distinct demo adapters (0 = plain params)")
    ap.add_argument("--adapter-rank", type=int, default=4)
    ap.add_argument("--requests", type=int, default=0,
                    help="continuous mode: requests to serve (0 = 2x slots)")
    ap.add_argument("--segment", type=int, default=8)
    ap.add_argument("--eos-id", type=int, default=-1)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    model_lib.require_decoder(cfg)
    if args.smoke:
        cfg = smoke_variant(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model_lib.init_params(key, cfg)
    prompts = jax.random.randint(jax.random.fold_in(key, 1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    cache = args.cache_len or (args.prompt_len + args.new_tokens)

    row_ids = None
    if args.adapters:
        from . import adapters as adapters_lib
        params = adapters_lib.demo_wrap(params, cfg, args.adapters,
                                        rank=args.adapter_rank,
                                        key=jax.random.fold_in(key, 2))
        row_ids = jnp.arange(args.batch, dtype=jnp.int32) % args.adapters

    res = {"arch": cfg.name, "mode": args.mode, "batch": args.batch,
           "prompt_len": args.prompt_len, "new_tokens": args.new_tokens,
           "adapters": args.adapters}

    if args.mode == "continuous":
        n_req = args.requests or 2 * args.batch
        prompts_np = np.asarray(
            jax.random.randint(jax.random.fold_in(key, 3),
                               (n_req, args.prompt_len), 0, cfg.vocab_size))
        reqs = [Request(rid=i, prompt=prompts_np[i], max_new=args.new_tokens,
                        adapter=(i % args.adapters) if args.adapters else 0)
                for i in range(n_req)]
        server = SlotServer(params, cfg, slots=args.batch, cache_len=cache,
                            segment=args.segment, eos_id=args.eos_id,
                            temperature=args.temperature, seed=args.seed)
        out = server.run(reqs)
        s = out["stats"]
        total = s["prefill_s"] + s["decode_s"]
        res.update({
            "requests": n_req, "segments": s["segments"],
            "prefill_sec": round(s["prefill_s"], 4),
            "decode_sec": round(s["decode_s"], 4),
            "prefill_tokens_per_sec": round(s["prefill_tok_s"], 1),
            "decode_tokens_per_sec": round(s["decode_tok_s"], 1),
            "sec": round(total, 2),
            "tokens_per_sec": round(s["decode_tokens"] / total, 1)
            if total > 0 else 0.0,
            "sample_row": out["outputs"][0]})
        print(json.dumps(res))
        return {**res, "outputs": out["outputs"]}

    ids = row_ids
    pre = _prefill_fn(cfg)
    if args.mode == "scan" and args.new_tokens > 1:
        dec = _scan_decode_fn(cfg, args.new_tokens - 1,
                              float(args.temperature))
    timing = {}

    def run_once(record: bool):
        state = model_lib.init_decode_state(cfg, args.batch, cache)
        jax.block_until_ready((params, prompts))   # fence before the clock
        t0 = time.perf_counter()
        logits, state = pre(params, prompts, state, ids)
        jax.block_until_ready(logits)
        t1 = time.perf_counter()
        tok0 = _sample(logits, key, args.temperature)
        if args.mode == "scan":
            if args.new_tokens > 1:
                toks, _ = dec(params, tok0, state, key, ids)
                jax.block_until_ready(toks)
                out = jnp.concatenate([prompts, tok0[:, None], toks], axis=1)
            else:
                out = jnp.concatenate([prompts, tok0[:, None]], axis=1)
        else:
            step = _eager_step_fn(cfg)
            k, tok, outl = key, tok0, [tok0]
            for _ in range(args.new_tokens - 1):
                k, sub = jax.random.split(k)
                logits_i, state = step(params, tok, state, ids)
                tok = _sample(logits_i, sub, args.temperature)
                outl.append(tok)
            jax.block_until_ready(tok)
            out = jnp.concatenate([prompts, jnp.stack(outl, axis=1)], axis=1)
        t2 = time.perf_counter()
        if record:
            timing["prefill_s"] = t1 - t0
            timing["decode_s"] = t2 - t1
        return out

    run_once(record=False)                 # compile warmup, not timed
    out = run_once(record=True)

    pf, dc = timing["prefill_s"], timing["decode_s"]
    total = pf + dc
    res.update({
        "prefill_sec": round(pf, 4), "decode_sec": round(dc, 4),
        "prefill_tokens_per_sec":
            round(args.batch * args.prompt_len / pf, 1) if pf > 0 else 0.0,
        "decode_tokens_per_sec":
            round(args.batch * args.new_tokens / dc, 1) if dc > 0 else 0.0,
        "sec": round(total, 2),
        "tokens_per_sec": round(args.batch * args.new_tokens / total, 1)
        if total > 0 else 0.0,
        "sample_row": out[0, -args.new_tokens:].tolist()})
    print(json.dumps(res))
    new = np.asarray(out[:, -args.new_tokens:])
    return {**res, "outputs": {i: new[i].tolist() for i in range(len(new))}}


if __name__ == "__main__":
    main()
