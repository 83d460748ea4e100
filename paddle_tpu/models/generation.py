"""Autoregressive generation — the serving path's model-side half.

≙ reference L10 inference engine's generation loop + PaddleNLP
`GenerationMixin` (SURVEY.md §1 L10, §7 step 6): greedy search and
sampling (temperature / top-k / top-p) over a static-shape KV cache.

TPU-first design: the ENTIRE generation — prefill + `lax.scan` over decode
steps — is ONE compiled XLA program (compiled once per
(batch, prompt_len, max_new_tokens) signature and cached on the model).
The reference drives its decode loop from C++ with per-step kernel
launches («fused_multi_transformer» [U]); under XLA the loop body is a
traced region, so there is no per-token dispatch at all. The KV cache is
donated through the scan carry and updated in place in HBM.

This is the REFERENCE decode stack: `generate()` through the dense
(B, S, HK, D) tuple cache shares no cache code with the serving engine
(`models/serving.py`, paged pools + the ragged kernel), and the
engine's greedy streams are held to it request by request
(tests/test_serving.py, tests/test_ragged_attention.py).

The model must implement `forward(input_ids, past_key_values=...,
position_offset=..., use_cache=True)` returning (logits, caches) — see
LlamaForCausalLM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.core.tensor import Tensor
from paddle_tpu.autograd import no_grad
from paddle_tpu.observability import span as telemetry_span
from paddle_tpu.tensor.random import default_generator

NEG_INF = -1e30


class RequestStatus:
    """Request lifecycle states shared by the serving engine and any
    generation-level caller that tracks in-flight work (≙ the reference
    serving stack's per-request state machine). A request is QUEUED on
    admission-queue entry, RUNNING while it owns a slot, and ends in
    exactly one terminal state: FINISHED (eos / max_new_tokens / cache
    end), TIMEOUT (deadline or max_queue_time expired), FAILED (prefill
    or dispatch error — the engine keeps serving others), or PREEMPTED
    (evicted for pool pressure more than `max_preemptions` times —
    the starvation guard)."""

    QUEUED = "queued"
    RUNNING = "running"
    FINISHED = "finished"
    TIMEOUT = "timeout"
    FAILED = "failed"
    PREEMPTED = "preempted"
    TERMINAL = frozenset({FINISHED, TIMEOUT, FAILED, PREEMPTED})


def _sample_token(logits, key, strategy, temperature, top_k, top_p):
    """logits: (B, V) f32 -> (tokens (B,), log-prob of chosen (B,))."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    if strategy == "greedy_search":
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return tok, jnp.take_along_axis(logp, tok[:, None], -1)[:, 0]
    # sampling
    if temperature != 1.0:
        logits = logits / temperature
    if top_k and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p (always
        # keep the most likely token)
        keep_sorted = cum - probs < top_p
        cutoff = jnp.min(
            jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1)
        logits = jnp.where(logits < cutoff[:, None], NEG_INF, logits)
    tok = jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
    return tok, jnp.take_along_axis(logp, tok[:, None], -1)[:, 0]


def _ban_repeat_ngrams(logits, buf, cur, n):
    """no_repeat_ngram_size processor: ban every token v that would
    complete an n-gram already present in `buf[:, :cur]` (prompt +
    emitted so far). buf: (R, L) int32; cur: traced scalar count of
    valid tokens; logits: (R, V). All static shapes — windows over the
    whole buffer, invalid ones masked."""
    r, L = buf.shape
    v_size = logits.shape[-1]
    if L < n:
        return logits
    # the (n-1)-token suffix being extended
    suffix = jax.lax.dynamic_slice_in_dim(
        buf, jnp.maximum(cur - (n - 1), 0), n - 1, 1)       # (R, n-1)
    starts = jnp.arange(L - n + 1)
    win_idx = starts[:, None] + jnp.arange(n - 1)[None, :]
    windows = buf[:, win_idx]                                # (R, W, n-1)
    match = jnp.all(windows == suffix[:, None, :], -1) \
        & (starts[None, :] <= cur - n)                       # (R, W)
    ban_tok = buf[jnp.arange(r)[:, None], starts[None, :] + n - 1]
    banned = jnp.zeros((r, v_size + 1), bool).at[
        jnp.arange(r)[:, None],
        jnp.where(match, ban_tok, v_size)].set(True)[:, :v_size]
    return jnp.where(banned, NEG_INF, logits)


def _penalize(logits, seen, t, rp, min_new, eos):
    """Logit post-processing shared by every decode strategy (≙ the
    reference's LogitsProcessor stack): CTRL-style repetition penalty on
    already-seen tokens (positive logits divided by rp, negative
    multiplied), and EOS suppression while fewer than `min_new_tokens`
    tokens have been generated. `t` is the index of the token being
    generated; `seen` is a (..., V) presence mask."""
    if rp != 1.0:
        pen = jnp.where(logits > 0, logits / rp, logits * rp)
        logits = jnp.where(seen, pen, logits)
    if eos is not None and min_new > 0:
        col = jnp.arange(logits.shape[-1]) == eos
        logits = jnp.where(col & (t < min_new), NEG_INF, logits)
    return logits


class bind_state:
    """Context manager: temporarily install traced param/buffer values
    on a model's live Parameter/Tensor objects (the jit-harness pattern
    every compiled model program uses — generate, continuous-batching
    prefill/decode). Restores the originals on exit, exception-safe."""

    def __init__(self, params, buffers, pv, bv):
        self.params, self.buffers = params, buffers
        self.pv, self.bv = pv, bv

    def __enter__(self):
        self._old_p = [p._value for p in self.params]
        self._old_b = [b._value for b in self.buffers]
        for p, v in zip(self.params, self.pv):
            p._value = v
        for b, v in zip(self.buffers, self.bv):
            b._value = v
        return self

    def __exit__(self, *exc):
        for p, v in zip(self.params, self._old_p):
            p._value = v
        for b, v in zip(self.buffers, self._old_b):
            b._value = v
        return False


class GenerationMixin:
    """Mixin over cache-capable causal LMs; adds `generate()`.

    ≙ PaddleNLP `GenerationMixin.generate` surface (greedy_search /
    sampling / beam_search strategies; returns (ids, scores) like the
    reference — for beam_search, ids is the best beam per row (B, n_new)
    and scores its length-penalty-normalized log-prob (B,))."""

    def generate(self, input_ids, max_new_tokens: int = 32,
                 decode_strategy: str = "greedy_search",
                 temperature: float = 1.0, top_k: int = 0,
                 top_p: float = 1.0, eos_token_id: int | None = None,
                 max_cache_len: int | None = None, use_cache: bool = True,
                 num_beams: int = 1, length_penalty: float = 0.0,
                 repetition_penalty: float = 1.0,
                 min_new_tokens: int = 0,
                 no_repeat_ngram_size: int = 0):
        if decode_strategy not in ("greedy_search", "sampling",
                                   "beam_search"):
            raise ValueError(
                f"decode_strategy {decode_strategy!r}: greedy_search, "
                "sampling, or beam_search")
        if decode_strategy == "beam_search" and num_beams < 2:
            raise ValueError("beam_search needs num_beams >= 2")
        if repetition_penalty <= 0:
            raise ValueError(
                f"repetition_penalty must be > 0, got {repetition_penalty}"
                " (1.0 disables it)")
        if no_repeat_ngram_size < 0:
            raise ValueError(
                f"no_repeat_ngram_size must be >= 0, got "
                f"{no_repeat_ngram_size} (0 disables it)")
        cfg = self.config
        ids = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(jnp.asarray(input_ids, jnp.int32))
        b, prompt_len = ids.shape
        n_new = int(max_new_tokens)
        cache_len = int(max_cache_len or min(cfg.max_position_embeddings,
                                             prompt_len + n_new))
        if prompt_len + n_new > cache_len:
            raise ValueError(
                f"prompt {prompt_len} + max_new_tokens {n_new} exceeds "
                f"cache length {cache_len}")

        params = list(self.parameters())
        buffers = list(self.buffers())
        key = default_generator.next_key()

        # the cached closure binds the param/buffer LISTS positionally,
        # so any structural change (e.g. weight-only quantization swaps
        # Linear params for int8 buffers) must invalidate it
        struct = (tuple((tuple(p.shape), str(p.dtype)) for p in params),
                  tuple((tuple(bu.shape), str(bu.dtype))
                        for bu in buffers))
        sig = (b, prompt_len, n_new, cache_len, decode_strategy,
               float(temperature), int(top_k), float(top_p), eos_token_id,
               struct, int(num_beams), float(length_penalty),
               float(repetition_penalty), int(min_new_tokens),
               int(no_repeat_ngram_size))
        cache = getattr(self, "_generate_cache", None)
        if cache is None or cache[0] != sig:
            with telemetry_span("generate.build",
                                strategy=decode_strategy, batch=b,
                                prompt_len=prompt_len, n_new=n_new):
                if decode_strategy == "beam_search":
                    jitted = self._build_beam_generate(sig)
                else:
                    jitted = self._build_generate(sig)
            self._generate_cache = (sig, jitted)
        else:
            jitted = cache[1]

        # one span for the whole compiled program: prefill + the decode
        # scan are a single dispatch, and generate() stays async — the
        # span times host dispatch; device time lives on the XLA
        # timeline via the span's RecordEvent interop
        with telemetry_span("generate.dispatch",
                            strategy=decode_strategy, batch=b,
                            prompt_len=prompt_len, n_new=n_new):
            toks, scores = jitted([p._value for p in params],
                                  [bu._value for bu in buffers],
                                  ids._value.astype(jnp.int32), key)
        return Tensor(toks), Tensor(scores)


    def _zero_caches_prefill(self, b, cache_len, kv_dtype, ids_v):
        """Shared by every generate builder: zero-init static KV caches
        and run the one-pass causal prefill. Returns (logits, caches)."""
        cfg = self.config
        caches = [
            (jnp.zeros((b, cache_len, cfg.num_key_value_heads,
                        cfg.head_dim), kv_dtype),
             jnp.zeros((b, cache_len, cfg.num_key_value_heads,
                        cfg.head_dim), kv_dtype))
            for _ in range(cfg.num_hidden_layers)]
        return self.forward(
            Tensor(ids_v),
            past_key_values=[(Tensor(k), Tensor(v)) for k, v in caches],
            position_offset=0, use_cache=True)

    def _build_generate(self, sig):
        (b, prompt_len, n_new, cache_len, strategy, temperature, top_k,
         top_p, eos_token_id, _struct) = sig[:10]
        rep_pen, min_new, ngram = sig[12], sig[13], sig[14]
        cfg = self.config
        params = list(self.parameters())
        buffers = list(self.buffers())
        n_layers = cfg.num_hidden_layers
        hk = cfg.num_key_value_heads
        hd = cfg.head_dim

        def run(pv, bv, ids_v, key):
            with bind_state(params, buffers, pv, bv):
                kv_dtype = pv[0].dtype
                with no_grad():
                    # ---- prefill: one causal pass over the prompt -------
                    logits, caches_t = self._zero_caches_prefill(
                        b, cache_len, kv_dtype, ids_v)
                    caches_v = tuple(
                        (k._value, v._value) for k, v in caches_t)
                    track = rep_pen != 1.0   # static: mask only if used
                    v_size = logits.shape[-1]
                    seen = (jnp.zeros((b, v_size), bool).at[
                        jnp.arange(b)[:, None], ids_v].set(True)
                        if track else jnp.zeros((), bool))
                    # full-sequence buffer for the n-gram ban (static
                    # L = prompt + n_new; only when the knob is on)
                    buf = (jnp.zeros((b, prompt_len + n_new),
                                     jnp.int32).at[:, :prompt_len].set(
                        ids_v.astype(jnp.int32))
                        if ngram else jnp.zeros((), jnp.int32))
                    key0, key_rest = jax.random.split(key)
                    lg0 = _penalize(logits._value[:, -1], seen, 0,
                                    rep_pen, min_new, eos_token_id)
                    if ngram:
                        lg0 = _ban_repeat_ngrams(
                            lg0, buf, jnp.int32(prompt_len), ngram)
                    tok0, lp0 = _sample_token(
                        lg0, key0, strategy, temperature, top_k, top_p)
                    if track:
                        seen = seen.at[jnp.arange(b), tok0].set(True)
                    if ngram:
                        buf = buf.at[:, prompt_len].set(tok0)
                    fin0 = (tok0 == eos_token_id) if eos_token_id is not None \
                        else jnp.zeros((b,), bool)

                    # ---- decode: lax.scan, one token per step -----------
                    def body(carry, t):
                        caches_v, tok, pos, fin, seen, buf, k = carry
                        k, sub = jax.random.split(k)
                        pkv = [(Tensor(kc), Tensor(vc))
                               for kc, vc in caches_v]
                        step_logits, new_caches = self.forward(
                            Tensor(tok[:, None]),
                            past_key_values=pkv,
                            position_offset=Tensor(pos), use_cache=True)
                        lg = _penalize(step_logits._value[:, 0], seen, t,
                                       rep_pen, min_new, eos_token_id)
                        if ngram:
                            lg = _ban_repeat_ngrams(
                                lg, buf, prompt_len + t, ngram)
                        nxt, lp = _sample_token(
                            lg, sub, strategy, temperature, top_k, top_p)
                        if eos_token_id is not None:
                            nxt = jnp.where(fin, eos_token_id, nxt)
                            lp = jnp.where(fin, 0.0, lp)
                            new_fin = fin | (nxt == eos_token_id)
                        else:
                            new_fin = fin
                        new_caches_v = tuple(
                            (kc._value, vc._value) for kc, vc in new_caches)
                        new_seen = (seen.at[jnp.arange(b), nxt].set(True)
                                    if track else seen)
                        new_buf = (buf.at[jnp.arange(b),
                                          prompt_len + t].set(nxt)
                                   if ngram else buf)
                        return ((new_caches_v, nxt, pos + 1, new_fin,
                                 new_seen, new_buf, k), (nxt, lp))

                    if n_new > 1:
                        carry0 = (caches_v, tok0,
                                  jnp.int32(prompt_len), fin0, seen,
                                  buf, key_rest)
                        _, (toks, lps) = jax.lax.scan(
                            body, carry0, jnp.arange(1, n_new))
                        toks = jnp.concatenate(
                            [tok0[:, None], toks.T], axis=1)
                        lps = jnp.concatenate([lp0[:, None], lps.T], axis=1)
                    else:
                        toks, lps = tok0[:, None], lp0[:, None]
                    return toks, lps

        return jax.jit(run)

    def _build_beam_generate(self, sig):
        """Beam search as ONE compiled program (≙ PaddleNLP
        `beam_search` decode strategy). TPU-native shape: the beam batch
        is a (B*K)-row decode; each scan step does one cached forward,
        joint top-k over (K*V) candidates, then a GATHER along the batch
        axis that reorders KV caches / finished flags / emitted
        sequences to the surviving beams (the XLA equivalent of the
        reference's `reorder_cache`). Finished beams extend only with
        EOS at zero added log-prob (score frozen); the best beam per
        batch row is chosen by length-penalty-normalized score
        `cum / len**length_penalty` (length_penalty=0 → raw sum, the
        reference default). Deterministic — the PRNG key is unused."""
        (b, prompt_len, n_new, cache_len, _strategy, _t, _tk, _tp,
         eos_token_id, _struct, num_beams, length_penalty,
         rep_pen, min_new, ngram) = sig
        cfg = self.config
        params = list(self.parameters())
        buffers = list(self.buffers())
        n_layers = cfg.num_hidden_layers
        hk = cfg.num_key_value_heads
        hd = cfg.head_dim
        K = num_beams
        NEG = jnp.float32(NEG_INF)

        def run(pv, bv, ids_v, key):
            del key
            with bind_state(params, buffers, pv, bv), no_grad():
                kv_dtype = pv[0].dtype
                logits, caches_t = self._zero_caches_prefill(
                    b, cache_len, kv_dtype, ids_v)
                v = logits.shape[-1]
                track = rep_pen != 1.0   # static: mask only if used
                seen0 = (jnp.zeros((b, v), bool).at[
                    jnp.arange(b)[:, None], ids_v].set(True)
                    if track else jnp.zeros((), bool))
                lg0 = _penalize(logits._value[:, -1].astype(jnp.float32),
                                seen0, 0, rep_pen, min_new, eos_token_id)
                if ngram:
                    buf0 = jnp.concatenate(
                        [ids_v.astype(jnp.int32),
                         jnp.zeros((b, n_new), jnp.int32)], 1)
                    lg0 = _ban_repeat_ngrams(
                        lg0, buf0, jnp.int32(prompt_len), ngram)
                logp0 = jax.nn.log_softmax(lg0)
                # K may exceed V (full-width search on tiny vocabs):
                # only V real beams exist after the first expansion; the
                # rest start DEAD at -inf and revive only if later steps
                # have fewer than K live candidates
                k0 = min(K, v)
                cum, tok0 = jax.lax.top_k(logp0, k0)           # (B, k0)
                if k0 < K:
                    cum = jnp.concatenate(
                        [cum, jnp.full((b, K - k0), NEG)], 1)
                    tok0 = jnp.concatenate(
                        [tok0, jnp.zeros((b, K - k0), tok0.dtype)], 1)
                # tile the prompt caches to the beam batch (B*K rows;
                # beam j of row i lives at i*K + j)
                caches_v = tuple(
                    (jnp.repeat(kc._value, K, 0),
                     jnp.repeat(vc._value, K, 0)) for kc, vc in caches_t)
                fin = (tok0 == eos_token_id) if eos_token_id is not None \
                    else jnp.zeros((b, K), bool)
                seqs = jnp.zeros((b, K, n_new),
                                 jnp.int32).at[:, :, 0].set(tok0)
                seen = (jnp.repeat(seen0[:, None], K, 1).at[
                    jnp.arange(b)[:, None], jnp.arange(K)[None, :],
                    tok0].set(True)                            # (B, K, V)
                    if track else jnp.zeros((), bool))
                L = prompt_len + n_new
                buf = (jnp.repeat(buf0[:, None], K, 1)
                       .at[:, :, prompt_len].set(tok0)
                       if ngram else jnp.zeros((), jnp.int32))
                if eos_token_id is not None:
                    eos_row = jnp.full((v,), NEG).at[eos_token_id].set(0.0)

                def body(carry, t):
                    caches_v, tok, cum, fin, seqs, seen, buf = carry
                    pkv = [(Tensor(kc), Tensor(vc))
                           for kc, vc in caches_v]
                    step_logits, new_caches = self.forward(
                        Tensor(tok.reshape(b * K)[:, None]),
                        past_key_values=pkv,
                        position_offset=Tensor(prompt_len - 1 + t),
                        use_cache=True)
                    lgf = _penalize(
                        step_logits._value[:, 0].astype(jnp.float32),
                        seen.reshape(b * K, v) if track else seen,
                        t, rep_pen, min_new, eos_token_id)
                    if ngram:
                        lgf = _ban_repeat_ngrams(
                            lgf, buf.reshape(b * K, L), prompt_len + t,
                            ngram)
                    lgp = jax.nn.log_softmax(lgf).reshape(b, K, v)
                    if eos_token_id is not None:
                        lgp = jnp.where(fin[:, :, None],
                                        eos_row[None, None, :], lgp)
                    cand = cum[:, :, None] + lgp               # (B, K, V)
                    ncum, flat = jax.lax.top_k(cand.reshape(b, K * v), K)
                    src = flat // v                            # (B, K)
                    ntok = flat % v
                    gidx = (jnp.arange(b)[:, None] * K + src).reshape(-1)
                    new_caches_v = tuple(
                        (kc._value[gidx], vc._value[gidx])
                        for kc, vc in new_caches)
                    nfin = jnp.take_along_axis(fin, src, 1)
                    if eos_token_id is not None:
                        nfin = nfin | (ntok == eos_token_id)
                    nseqs = jnp.take_along_axis(
                        seqs, src[:, :, None], 1).at[:, :, t].set(ntok)
                    nseen = (jnp.take_along_axis(
                        seen, src[:, :, None], 1).at[
                        jnp.arange(b)[:, None], jnp.arange(K)[None, :],
                        ntok].set(True) if track else seen)
                    nbuf = (jnp.take_along_axis(
                        buf, src[:, :, None], 1).at[
                        jnp.arange(b)[:, None], jnp.arange(K)[None, :],
                        prompt_len + t].set(ntok) if ngram else buf)
                    return (new_caches_v, ntok, ncum, nfin, nseqs,
                            nseen, nbuf), None

                if n_new > 1:
                    carry = (caches_v, tok0, cum, fin, seqs, seen, buf)
                    (caches_v, _, cum, fin, seqs, _, _), _ = jax.lax.scan(
                        body, carry, jnp.arange(1, n_new))
                if eos_token_id is not None:
                    iseos = seqs == eos_token_id
                    lengths = jnp.where(iseos.any(-1),
                                        jnp.argmax(iseos, -1) + 1, n_new)
                else:
                    lengths = jnp.full((b, K), n_new)
                norm = cum / jnp.power(lengths.astype(jnp.float32),
                                       jnp.float32(length_penalty))
                best = jnp.argmax(norm, axis=1)
                out = jnp.take_along_axis(
                    seqs, best[:, None, None], 1)[:, 0]        # (B, n_new)
                return out, jnp.take_along_axis(
                    norm, best[:, None], 1)[:, 0]              # (B,)

        return jax.jit(run)
