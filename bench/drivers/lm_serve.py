"""Driver for serving a language model through ``serve.Engine``.

Set-up builds the configuration's model at its stated depth, makes
random bfloat16 weights on the device from the seed, builds the engine
and compiles one prefill per padded prompt width the mix can draw and
the decode step.  The run's open-loop schedule comes from the mix (one
schedule for every seed); the seed draws the prompts' tokens, Zipf over
the vocabulary in a seeded order, and which requests the check compares.

The window serves the schedule as it falls due: each pass of the loop
takes up to ``max_batch`` requests due by then as one batch, right-pads
their prompts to a multiple of ``pad_multiple``, fills the rest of the
batch with inert rows (length 0), prefills with per-row lengths and
decodes ``new_tokens - 1`` greedy steps.  A request is timed from its due
time until its last token is on the host; requests due in the window and
not yet served when it closes are served after it and counted.

The check compares, for a seeded sample of the window's requests, the
program's logits at the prefill's last position and at decode steps 0,
63, 127 and the last with the plain float32 reference
(``bench/refs/deepseek_v2.py``), teacher-forced on the program's own
tokens; and it requires that no MoE assignment was dropped and that every
request was answered.
"""
from __future__ import annotations

import time
from typing import Dict, Iterator, List, Tuple

import numpy as np

from bench import lm_cost, traffic as traffic_gen
from bench.harness import log
from bench.refs import deepseek_v2 as ref

# decode steps whose logits the check compares (the last is added)
CAPTURE_STEPS = (0, 63, 127)
SAMPLE = 10                 # requests compared with the reference
# largest relative L2 error of the program's logits against the float32
# reference: the program's bfloat16 activations through 7 layers read up
# to 0.062 on a v5e, the float8 reference 0.375 and top-k renormalised
# 0.385 (PERF.md section 2)
LOGIT_LIMIT = 0.15


# the configuration file's published keys and the program's fields
FIELDS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab_size",
          "kv_lora_rank": "kv_lora_rank", "qk_rope_head_dim": "qk_rope_dim",
          "qk_nope_head_dim": "qk_nope_dim", "v_head_dim": "v_head_dim",
          "n_routed_experts": "n_experts", "n_shared_experts":
          "n_shared_experts", "num_experts_per_tok": "top_k",
          "moe_intermediate_size": "d_expert", "intermediate_size":
          "d_ff_dense", "first_k_dense_replace": "first_dense_layers",
          "norm_topk_prob": "norm_topk_prob", "rope_theta": "rope_theta",
          "num_hidden_layers": "n_layers"}
# the program applies the routed experts' gates unscaled
ROUTED_SCALING = 1.0
YARN = {"factor": "factor", "beta_fast": "beta_fast", "beta_slow":
        "beta_slow", "mscale": "mscale", "mscale_all_dim": "mscale_all_dim",
        "original_max_position_embeddings":
            "original_max_position_embeddings"}


def model_config(cfg: Dict):
    """The program's configuration of ``cfg["arch"]`` at the file's depth
    (and any ``overrides``, for tests at a tiny size); every published key
    the file states must be what the program runs."""
    from repro.configs import get_config
    mc = get_config(cfg["arch"]).replace(n_layers=cfg["num_hidden_layers"],
                                         **cfg.get("overrides", {}))
    if cfg.get("overrides"):
        return mc
    bad = {k: (cfg[k], getattr(mc, f)) for k, f in FIELDS.items()
           if k in cfg and cfg[k] != getattr(mc, f)}
    ys = cfg.get("rope_scaling")
    bad.update({f"rope_scaling.{k}": (ys[k], getattr(mc.rope_scaling, f))
                for k, f in YARN.items()
                if ys and ys[k] != getattr(mc.rope_scaling, f)})
    if cfg.get("routed_scaling_factor", ROUTED_SCALING) != ROUTED_SCALING:
        bad["routed_scaling_factor"] = (cfg["routed_scaling_factor"],
                                        ROUTED_SCALING)
    if (cfg.get("q_lora_rank") or 0) != mc.q_lora_rank:
        bad["q_lora_rank"] = (cfg["q_lora_rank"], mc.q_lora_rank)
    if cfg.get("tie_word_embeddings", False) != mc.tie_embeddings:
        bad["tie_word_embeddings"] = (cfg["tie_word_embeddings"],
                                      mc.tie_embeddings)
    if bad:
        raise ValueError(f"{cfg['arch']}: the file and the program differ "
                         f"(file, program): {bad}")
    return mc


def reference_config(mc) -> Dict:
    """The model's published keys, as the reference reads them."""
    ys = mc.rope_scaling
    return {
        "hidden_size": mc.d_model, "num_attention_heads": mc.n_heads,
        "kv_lora_rank": mc.kv_lora_rank, "qk_rope_head_dim": mc.qk_rope_dim,
        "qk_nope_head_dim": mc.qk_nope_dim, "v_head_dim": mc.v_head_dim,
        "n_routed_experts": mc.n_experts, "num_experts_per_tok": mc.top_k,
        "norm_topk_prob": mc.norm_topk_prob,
        "routed_scaling_factor": ROUTED_SCALING,
        "rope_theta": mc.rope_theta, "rms_norm_eps": 1e-6,
        "vocab_size": mc.vocab_size,
        "rope_scaling": None if ys is None else {
            "factor": ys.factor, "beta_fast": ys.beta_fast,
            "beta_slow": ys.beta_slow, "mscale": ys.mscale,
            "mscale_all_dim": ys.mscale_all_dim,
            "original_max_position_embeddings":
                ys.original_max_position_embeddings},
    }


def rope_columns(q_proj, kv_a_proj, mc):
    """The two projections with their rope output columns reordered from
    the program's half-split pairs (i, i + R/2) to the reference's
    interleaved pairs (2i, 2i + 1): the same rotation of the same values.
    ``q_proj``:(d, H*(nope+R)) holds each head's rope columns last,
    ``kv_a_proj``:(d, kv_lora+R) the shared key's."""
    import jax.numpy as jnp
    r, half = mc.qk_rope_dim, mc.qk_rope_dim // 2
    perm = np.stack([np.arange(half), np.arange(half) + half], -1).ravel()
    q = q_proj.reshape(q_proj.shape[0], mc.n_heads, -1)
    q = jnp.concatenate([q[..., :-r], q[..., -r:][..., perm]], -1)
    kv = jnp.concatenate([kv_a_proj[:, :-r], kv_a_proj[:, -r:][:, perm]], -1)
    return q.reshape(q_proj.shape), kv


def reference_layers(params, mc) -> Iterator[Tuple[str, Dict]]:
    """The program's layers in the reference's names and layouts, one at
    a time: per head the key-value up-projection is [k_nope | v], and the
    rope columns pair as DeepSeek's do (:func:`rope_columns`)."""
    import jax.numpy as jnp
    h, r = mc.n_heads, mc.kv_lora_rank
    kinds = mc.layer_pattern()
    prefix, unit, reps, rem = mc.pattern_unit()
    stacked = []
    for i in range(reps):
        for j in range(len(unit)):
            stacked.append((j, i))
    flat = ([("prefix", i) for i in range(len(prefix))]
            + [("unit", s) for s in stacked]
            + [("rem", i) for i in range(len(rem))])
    for kind, (where, at) in zip(kinds, flat):
        if where == "unit":
            j, i = at
            p = _index(params["unit"][j], i)
        else:
            p = params[where][at]
        a = p["attn"]
        uk = a["w_uk"].reshape(r, h, -1)
        uv = a["w_uv"].reshape(r, h, -1)
        q_proj, kv_a_proj = rope_columns(a["wq"], a["w_dkv"], mc)
        w = {"input_norm": p["ln1"]["w"], "post_norm": p["ln2"]["w"],
             "q_proj": q_proj, "kv_a_proj": kv_a_proj,
             "kv_a_norm": a["kv_norm"]["w"],
             "kv_b_proj": jnp.concatenate([uk, uv], -1).reshape(r, -1),
             "o_proj": a["wo"]}
        f = p["ffn"]
        if kind == "moe":
            w.update(router=f["router"], experts_gate=f["we_g"],
                     experts_up=f["we_u"], experts_down=f["we_d"],
                     shared_gate=f["shared"]["wg"],
                     shared_up=f["shared"]["wu"],
                     shared_down=f["shared"]["wd"])
            yield "moe", w
        else:
            w.update(gate=f["wg"], up=f["wu"], down=f["wd"])
            yield "dense", w


def _index(tree, i):
    import jax
    return jax.tree.map(lambda a: a[i], tree)


def reference_logits(params, mc, tokens, at, low=None, width=None):
    return ref.forward(tokens, params["embed"]["emb"],
                       params["final_norm"]["w"], params["embed"]["head"],
                       reference_layers(params, mc), reference_config(mc),
                       at, low=low, width=width)


# --- set-up -------------------------------------------------------------------

def widths(cfg: Dict, mix: Dict) -> List[int]:
    m = cfg["pad_multiple"]
    lo = -(-mix["n_min"] // m) * m
    hi = -(-mix["n_max"] // m) * m
    return list(range(lo, hi + 1, m))


def build(cfg: Dict, seed: int, mc=None):
    import jax
    from repro.models import model as M
    from repro.serve.engine import Engine
    mc = mc or model_config(cfg)
    params = jax.jit(M.init, static_argnums=0)(mc, jax.random.PRNGKey(seed))
    eng = Engine(mc, params, max_batch=cfg["max_batch"],
                 max_seq=cfg["max_seq"])
    return mc, params, eng


def setup(cfg: Dict, mix: Dict, seed: int, seconds: float) -> Dict:
    if mix["n_max"] + mix["new_tokens"] > cfg["max_seq"]:
        raise ValueError(f"mix n_max {mix['n_max']} + {mix['new_tokens']} "
                         f"new tokens is over max_seq {cfg['max_seq']}")
    mc, params, eng = build(cfg, seed)
    state = {"cfg": cfg, "mix": mix, "mc": mc, "params": params,
             "engine": eng}
    warm_up(state)
    schedule(state, mix, seed, seconds)
    return state


def warm_up(state: Dict) -> None:
    """One prefill per padded width and two decode steps, on inert rows."""
    eng, cfg = state["engine"], state["cfg"]
    b = cfg["max_batch"]
    for w in widths(cfg, state["mix"]):
        first = eng.prefill(np.zeros((b, w), np.int32),
                            np.zeros((b,), np.int32))
    eng.decode(first, 2)
    log(f"lm_serve: {state['mc'].name} at {state['mc'].n_layers} layers, "
        f"prefill widths {widths(cfg, state['mix'])} x {b} rows and the "
        f"decode step warmed")


def _zipf_tokens(rng, vocab: int, s: float, n: int, perm) -> np.ndarray:
    cdf = np.cumsum(np.arange(1, vocab + 1, dtype=np.float64) ** -s)
    rank = np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right")
    return perm[np.minimum(rank, vocab - 1)].astype(np.int32)


def schedule(state: Dict, mix: Dict, seed: int, seconds: float) -> None:
    """The run's requests: the open-loop schedule, each prompt's tokens
    and the sample the check compares, drawn from the seed."""
    sched = traffic_gen.open_loop(mix, 1, seed, seconds)
    rng = np.random.default_rng([seed, 1])
    vocab = state["mc"].vocab_size
    perm = rng.permutation(vocab)
    lens = sched["n"].astype(np.int64)
    toks = _zipf_tokens(rng, vocab, float(mix["token_zipf_s"]),
                        int(lens.sum()), perm)
    cuts = np.cumsum(lens)[:-1]
    prompts = np.split(toks, cuts)
    total = len(prompts)
    sample = np.sort(rng.choice(total, size=min(SAMPLE, total),
                                replace=False))
    state.update(prompts=prompts, due=sched["due_s"], sample=set(sample.tolist()))
    log(f"lm_serve: {total} requests due in {seconds} s, prompts "
        f"{int(lens.min())}-{int(lens.max())} tokens, {mix['new_tokens']} "
        f"new tokens each; {len(sample)} compared with the reference")


# --- window -------------------------------------------------------------------

def _capture_steps(new_tokens: int) -> Tuple[int, ...]:
    last = new_tokens - 2
    return tuple(sorted({s for s in CAPTURE_STEPS if s <= last} | {last}))


def serve_batch(state: Dict, idx: List[int], tracer, t0: float,
                events: List, captured: Dict) -> np.ndarray:
    """Serve requests ``idx`` as one batch; returns their tokens
    (len(idx), new_tokens).  Appends (host time, kind, flops, bytes) per
    program run to ``events`` (for a decode step, its rows' lengths in
    place of the bytes) and keeps the logits arrays that hold the sampled
    rows (a reference each, so nothing new is compiled or run)."""
    import jax.numpy as jnp
    cfg, mc, eng = state["cfg"], state["mc"], state["engine"]
    new = state["mix"]["new_tokens"]
    b, m = cfg["max_batch"], cfg["pad_multiple"]
    lens = np.array([len(state["prompts"][i]) for i in idx], np.int64)
    width = int(-(-lens.max() // m) * m)
    prompts = np.zeros((b, width), np.int32)
    lengths = np.zeros((b,), np.int32)
    for row, i in enumerate(idx):
        prompts[row, :lens[row]] = state["prompts"][i]
        lengths[row] = lens[row]
    rows = [row for row, i in enumerate(idx) if i in state["sample"]]
    steps = _capture_steps(new)
    grab = {}           # the engine's logits arrays, kept as they are

    events.append((time.perf_counter() - t0, "prefill",
                   lm_cost.prefill_flops(mc, lens), None))
    first = eng.prefill(jnp.asarray(prompts), lengths)
    if rows:
        grab["prefill"] = eng.logits

    def on_step(i):
        tracer.tick(time.perf_counter() - t0)
        if rows and i in steps:
            grab[i] = eng.logits
        if i + 1 < new - 1:
            events.append((time.perf_counter() - t0, "decode",
                           lm_cost.decode_flops(mc, lens + i + 1),
                           lens + i + 1))

    events.append((time.perf_counter() - t0, "decode",
                   lm_cost.decode_flops(mc, lens), lens))
    rest = eng.decode(first, new - 1, on_step=on_step)
    out = np.concatenate([np.asarray(first)[:, None], rest], axis=1)
    for row in rows:
        captured[idx[row]] = {s: (g, row) for s, g in grab.items()}
    return out[:len(idx)]


def window(state: Dict, seconds: float, tracer) -> Dict:
    eng, prompts, due = state["engine"], state["prompts"], state["due"]
    cfg = state["cfg"]
    total = len(prompts)
    lat = np.zeros(total)
    wait = np.zeros(total)
    outputs = [None] * total
    events, captured, batches = [], {}, []
    s0 = eng.stats()
    t0 = time.perf_counter()
    i = 0
    while i < total:
        now = time.perf_counter() - t0
        tracer.tick(now)
        if due[i] > now:
            with tracer.span("bench.wait"):
                time.sleep(due[i] - now)
            continue
        j = min(int(np.searchsorted(due, now, side="right")),
                i + cfg["max_batch"])
        t_sub = time.perf_counter() - t0
        idx = list(range(i, j))
        with tracer.span("bench.submit"):
            try:
                res = serve_batch(state, idx, tracer, t0, events, captured)
            except Exception as e:  # noqa: BLE001 — counted, then checked
                log(f"lm_serve: batch {idx[0]}-{idx[-1]} failed: {e!r}")
                res = [e] * len(idx)
        t_done = time.perf_counter() - t0
        wait[i:j] = t_sub - due[i:j]
        lat[i:j] = t_done - due[i:j]
        outputs[i:j] = list(res)
        batches.append((t_sub, i, j))
        i = j
    while time.perf_counter() - t0 < seconds:   # the window's full length
        tracer.tick(time.perf_counter() - t0)
        with tracer.span("bench.wait"):
            time.sleep(min(0.05, seconds - (time.perf_counter() - t0)))
    elapsed = time.perf_counter() - t0
    s1 = eng.stats()
    state.update(outputs=outputs, captured=captured)
    delta = {k: s1[k] - s0[k] for k in s1}
    delta["moe_expert_tokens"] = delta["moe_expert_tokens"].tolist()
    # the prefill's padding, under the keys ``pad_overhead`` reads
    delta["payload_elems"] = delta["prefill_tokens"]
    delta["padded_elems"] = delta["prefill_padded_tokens"]
    # bytes of each decode step: experts touched at the window's mean
    touched = delta["decode_experts_touched"] / max(1, delta["decode_steps"])
    events = [(t, kind, flops,
               0.0 if kind == "prefill"
               else lm_cost.decode_bytes(state["mc"], lens, touched))
              for t, kind, flops, lens in events]
    log(f"lm_serve: served {total} requests in {len(batches)} batches over "
        f"{elapsed:.3f} s; prefill tokens {delta['prefill_tokens']} live of "
        f"{delta['prefill_padded_tokens']}, {delta['decode_steps']} decode "
        f"steps, {delta['decode_rows_live']} live rows, moe_dropped "
        f"{s1['moe_dropped']} since the engine was built")
    return {"attempted": total, "elapsed_s": elapsed,
            "latency_ms": lat * 1e3, "queue_wait_ms": wait * 1e3,
            "slates": [(t, j - i) for t, i, j in batches],
            "events": events, "engine": delta,
            "moe_dropped": s1["moe_dropped"],
            "failed": sum(isinstance(o, Exception) for o in outputs),
            "trace_start_s": (None if tracer.t_start is None
                              else tracer.t_start - t0)}


# --- check ----------------------------------------------------------------------

def _compared(state: Dict, idx: int, toks):
    """A request's teacher-forced tokens, the compared positions and the
    captured logits' keys."""
    new = state["mix"]["new_tokens"]
    steps = _capture_steps(new)
    prompt = state["prompts"][idx]
    n = len(prompt)
    seq = np.concatenate([prompt, np.asarray(toks)[:new - 1]])
    return seq, [n - 1] + [n + s for s in steps], ["prefill"] + list(steps)


def logit_errors(state: Dict, outputs, captured, keep=None) -> List[float]:
    """Per sampled request, the largest relative L2 error of its captured
    logits against the reference, teacher-forced on its own tokens (the
    reference's logits go into ``keep``, by request, if given)."""
    mc, params = state["mc"], state["params"]
    errs = []
    for idx in sorted(captured):
        toks = outputs[idx]
        if toks is None or isinstance(toks, Exception):
            continue
        seq, at, keys = _compared(state, idx, toks)
        want = reference_logits(params, mc, seq, at,
                                width=state["cfg"]["max_seq"])
        if keep is not None:
            keep[idx] = want
        got = {k: np.asarray(g[row], np.float32)[:mc.vocab_size]
               for k, (g, row) in captured[idx].items()}
        errs.append(max(ref.rel_err(got[k], want[q])
                        for q, k in enumerate(keys)))
    return errs


def answers(state: Dict, outputs, captured, dropped: int) -> Dict:
    unanswered = sum(o is None or isinstance(o, Exception) for o in outputs)
    errs = logit_errors(state, outputs, captured,
                        keep=state.setdefault("reference", {}))
    # fewer compared requests than drawn fails the check
    worst = (max(errs) if len(errs) >= min(SAMPLE, len(outputs))
             else float("inf"))
    log(f"lm_serve: logit errors of {len(errs)} requests: "
        f"{[round(e, 5) for e in errs]}")
    return {"logit_rel_err": {"value": worst, "limit": LOGIT_LIMIT},
            "moe_dropped": {"value": int(dropped), "limit": 0},
            "unanswered": {"value": unanswered, "limit": 0}}


def check(state: Dict, record: Dict):
    checks = answers(state, state["outputs"], state["captured"],
                     record["moe_dropped"])
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


# --- controls -------------------------------------------------------------------

def control_readings(state: Dict) -> Dict:
    """The check's numbers for four controls, each of which must fail it:
    the program with the top-k gates renormalised, with the YaRN factor
    left out of the softmax scale, with capacity dispatch and drops, and
    the reference itself one precision below the program's (float8) in
    the program's place.  The sampled requests are served again as one
    batch through each changed program.  Needs :func:`check` first."""
    import dataclasses
    from bench.harness import Tracer
    from repro.serve.engine import Engine
    cfg, mc = state["cfg"], state["mc"]
    sample = sorted(state["captured"])[:cfg["max_batch"]]
    no_m2 = dataclasses.replace(mc.rope_scaling, mscale=0.0,
                                mscale_all_dim=0.0)
    # (config, dropless): the last is the training dispatch, capacity
    # 1.25 with drops, in the serving modes
    variants = {
        "topk_renorm": (mc.replace(norm_topk_prob=True), True),
        "no_mscale_sq": (mc.replace(rope_scaling=no_m2), True),
        "capacity_drops": (mc, False),
    }
    outs = {}
    for name, (vc, dropless) in variants.items():
        eng = Engine(vc, state["params"], max_batch=cfg["max_batch"],
                     max_seq=cfg["max_seq"], dropless=dropless)
        sub = dict(state, mc=vc, engine=eng, sample=set(sample))
        captured = {}
        toks = serve_batch(sub, sample, Tracer(False, "", 0.0),
                           time.perf_counter(), [], captured)
        outputs = [None] * len(state["outputs"])
        for k, i in enumerate(sample):
            outputs[i] = toks[k]
        outs[f"control.{name}.logit_rel_err"] = max(
            logit_errors(state, outputs, captured))
        outs[f"control.{name}.moe_dropped"] = eng.stats()["moe_dropped"]
        del eng
    errs = []
    for idx in sample:
        seq, at, _ = _compared(state, idx, state["outputs"][idx])
        low = reference_logits(state["params"], mc, seq, at,
                               low="float8_e4m3fn", width=cfg["max_seq"])
        want = state["reference"][idx]
        errs.append(max(ref.rel_err(low[q], want[q])
                        for q in range(len(at))))
    outs["control.reference_float8.logit_rel_err"] = max(errs)
    return outs


def readings(state: Dict, record: Dict) -> Dict:
    """For setting and checking the limits: the program's numbers and the
    controls'."""
    out = {k: c["value"] for k, c in check(state, record)[0].items()}
    out.update(control_readings(state))
    return out
