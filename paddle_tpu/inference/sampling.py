"""Per-request sampling for the serving engine (ROADMAP item 4).

Everything the engine served before this module was greedy argmax.
Real traffic wants temperature / nucleus / top-k sampling with
per-request seeds, per-request stop tokens, logit bias, and a
constraint hook for structured decoding — WITHOUT forking the compiled
program per sampler configuration. The design puts every sampler knob
in runtime *data*:

- :class:`SamplingParams` is the per-request spec. The engine packs one
  row of ``[R]``-shaped device arrays per live request (temperature,
  top_p, top_k, seed, bias/constraint slots), so a greedy row, a
  temperature-1.0 row and a top-p row ride the SAME dispatch of the
  SAME executable. Greedy rows (``temperature == 0``) take the argmax
  of the exact same logits the old program argmaxed — token-for-token
  bitwise-identical outputs by construction.
- :func:`sampled_next_tokens` is the vectorized sample step compiled
  into the mixed program (:meth:`LlamaServingEngine._mixed_forward`),
  next to the existing argmax. Randomness is counter-based: each row
  derives ``fold_in(PRNGKey(seed), position)`` — the threefry key is a
  pure function of (request seed, absolute token position), never of
  dispatch shape, batch composition, scan length, or acceptance
  history. That is what makes the speculative engine's outputs
  *sample-exact* against the non-speculative engine (same seed ⇒ same
  sequence, speculation on or off — the distribution-exactness gate).

Speculative verification under sampling (rejection sampling):
  the drafter is deterministic (a point mass ``q = δ(draft)``), so the
  textbook accept rule ``accept w.p. min(1, p(draft)/q(draft)) =
  p(draft)``, resample-from-residual-on-reject, is implemented exactly
  by sampling the target's own token ``t ~ p`` with the position's
  counter key and accepting the draft iff ``draft == t``:
  ``P(accept) = P(t = draft) = p(draft)``, and on reject the emitted
  token IS ``t`` conditioned on ``t ≠ draft`` — precisely the residual
  ``max(0, p - q)`` renormalized. One rule covers greedy (argmax is a
  point-mass target) and sampled rows, and the engine's existing
  longest-matching-prefix accept loop needs no change — ``out[f+j]``
  simply holds the sampled token instead of the argmax.

Structured decoding rides the same row slots: ``logit_bias`` entries
scatter-add into the row's logits, and a ``constraint`` hook narrows
the next token to an explicit allowed set (everything else masked to
-inf) — both bounded by the engine's static ``sample_slots`` width so
compiled shapes never fork per request.
"""

from __future__ import annotations

import math

__all__ = ["SamplingParams", "GREEDY", "sampled_next_tokens"]

#: Sentinel large-negative logit used to mask tokens out of the
#: sampled distribution (finite so softmax/cumsum stay NaN-free).
_MASKED = -1e30


class SamplingParams:
    """Per-request sampling spec. All fields are runtime data — two
    requests with different params share one compiled program.

    Args:
        temperature: 0 (default) = greedy argmax, bitwise-identical to
            the pre-sampling engine. > 0 scales logits before sampling.
        top_p: nucleus mass in (0, 1]; 1.0 disables.
        top_k: keep the k highest-probability tokens; 0 disables.
        seed: per-request RNG seed (int). ``None`` lets the engine
            assign one at admission (recorded on the request so the
            draw is reproducible after the fact). The sampled sequence
            is a pure function of (model, prompt, params, seed) —
            independent of batch composition, scan lengths, and
            speculation.
        stop: iterable of *token ids*; generation retires as
            ``completed`` right before any of them would be appended
            (the stop token is excluded from the output).
        logit_bias: ``{token_id: additive_logit_bias}`` applied every
            step (OpenAI semantics). Bounded by the engine's
            ``sample_slots`` width.
        constraint: optional hook for structured decoding:
            ``fn(prompt_ids, output_ids) -> allowed_token_ids | None``.
            Called at each step's schedule time on the host; a non-None
            return masks every OTHER token to -inf, so the next token
            is sampled (or argmaxed) from the allowed set only. Return
            ``None`` for "unconstrained this step". The allowed set is
            bounded by ``sample_slots``; hooks cannot cross a
            subprocess-replica boundary (in-process engines/replicas
            only).
    """

    __slots__ = ("temperature", "top_p", "top_k", "seed", "stop",
                 "logit_bias", "constraint")

    def __init__(self, temperature=0.0, top_p=1.0, top_k=0, seed=None,
                 stop=(), logit_bias=None, constraint=None):
        temperature = float(temperature)
        if not math.isfinite(temperature) or temperature < 0:
            raise ValueError(
                f"temperature must be finite and >= 0, got {temperature}")
        top_p = float(top_p)
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        top_k = int(top_k)
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 = off), got {top_k}")
        if seed is not None:
            seed = int(seed)
            if not 0 <= seed < 2 ** 31:
                raise ValueError(
                    f"seed must be in [0, 2**31), got {seed}")
        stop = tuple(int(t) for t in (stop or ()))
        if logit_bias:
            logit_bias = {int(k): float(v)
                          for k, v in dict(logit_bias).items()}
            for v in logit_bias.values():
                if not math.isfinite(v):
                    raise ValueError("logit_bias values must be finite")
        else:
            logit_bias = None
        if constraint is not None and not callable(constraint):
            raise ValueError("constraint must be callable "
                             "(prompt_ids, output_ids) -> ids | None")
        self.temperature = temperature
        self.top_p = top_p
        self.top_k = top_k
        self.seed = seed
        self.stop = stop
        self.logit_bias = logit_bias
        self.constraint = constraint

    @property
    def is_greedy(self):
        return self.temperature == 0.0

    def __repr__(self):
        return (f"SamplingParams(temperature={self.temperature}, "
                f"top_p={self.top_p}, top_k={self.top_k}, "
                f"seed={self.seed}, stop={self.stop}, "
                f"logit_bias={self.logit_bias}, "
                f"constraint={'set' if self.constraint else None})")

    # -- rpc plumbing ---------------------------------------------------
    def to_spec(self):
        """JSON-able dict for the subprocess-replica submit spec.
        Constraint hooks are host callables and cannot cross the
        process boundary — typed error, never a silent drop."""
        if self.constraint is not None:
            raise ValueError(
                "SamplingParams.constraint is a host callable and "
                "cannot cross a subprocess-replica boundary; use an "
                "in-process engine/replica for constrained decoding")
        return {"temperature": self.temperature, "top_p": self.top_p,
                "top_k": self.top_k, "seed": self.seed,
                "stop": list(self.stop),
                "logit_bias": {str(k): v for k, v
                               in (self.logit_bias or {}).items()}}

    @classmethod
    def from_spec(cls, spec):
        if spec is None:
            return None
        return cls(temperature=spec.get("temperature", 0.0),
                   top_p=spec.get("top_p", 1.0),
                   top_k=spec.get("top_k", 0),
                   seed=spec.get("seed"),
                   stop=spec.get("stop") or (),
                   logit_bias={int(k): float(v) for k, v in
                               (spec.get("logit_bias") or {}).items()})


#: Shared default: plain greedy decode, no stops, no bias.
GREEDY = SamplingParams()


def sampled_next_tokens(logits, temps, top_ps, top_ks, seeds, positions,
                        slot_ids, slot_vals, cmodes):
    """Vectorized per-row next-token rule — the pure-jax payload the
    engine wraps in a ``run_op`` inside the compiled mixed program.

    Args (jax arrays):
        logits:    [N, V] model logits (any float dtype).
        temps:     [N] f32, 0 = greedy (bitwise argmax of ``logits``).
        top_ps:    [N] f32 in (0, 1].
        top_ks:    [N] i32, 0 = off.
        seeds:     [N] i32 per-request seeds.
        positions: [N] i32 absolute position of the token being
            sampled — the counter folded into the threefry key, so the
            draw at a position is independent of how it was dispatched
            (per-step, scan tick, or speculative verify row).
        slot_ids:  [N, B] i32 bias/constraint token ids (-1 = empty).
        slot_vals: [N, B] f32 additive logit bias per slot.
        cmodes:    [N] i32; 0 = bias-only, 1 = constraint row (tokens
            outside the row's non-negative slot ids are masked out).

    Returns [N] int64 next-token ids.
    """
    import jax
    import jax.numpy as jnp

    n, v = logits.shape
    l = logits.astype(jnp.float32)
    rows = jnp.arange(n, dtype=jnp.int32)
    # bias scatter-add: empty slots (id -1) clip to token 0 with value
    # 0.0 — adding +0.0 never changes a comparison, so greedy rows
    # with no bias keep the exact argmax of the raw logits
    l = l.at[rows[:, None], jnp.clip(slot_ids, 0, v - 1)].add(slot_vals)
    # constraint rows: only the listed (non-negative) slot ids survive
    tok = jnp.arange(v, dtype=jnp.int32)[None, None, :]
    allowed = jnp.any((slot_ids[:, :, None] == tok)
                      & (slot_ids[:, :, None] >= 0), axis=1)    # [N, V]
    l = jnp.where((cmodes[:, None] == 1) & ~allowed, _MASKED, l)
    greedy = jnp.argmax(l, axis=-1)

    def _sampled():
        ls = l / jnp.maximum(temps, 1e-6)[:, None]
        sl = jnp.sort(ls, axis=-1)[:, ::-1]              # descending
        kk = jnp.where(top_ks > 0, jnp.minimum(top_ks, v), v)
        kth = jnp.take_along_axis(sl, (kk - 1)[:, None], axis=1)
        sp = jax.nn.softmax(sl, axis=-1)
        cum_before = jnp.cumsum(sp, axis=-1) - sp
        # nucleus: keep the shortest prefix reaching top_p mass (the
        # first token crossing the boundary included); the mask is a
        # prefix of the sort, so its last kept value is a per-row
        # logit cutoff
        n_keep = jnp.maximum(
            jnp.sum(cum_before < top_ps[:, None], axis=-1), 1)
        pth = jnp.take_along_axis(sl, (n_keep - 1)[:, None], axis=1)
        keep = ls >= jnp.maximum(kth, pth)
        # counter-based randomness: key = fold_in(PRNGKey(seed),
        # position) — a pure function of (seed, position), nothing else
        def _gumbel(seed, pos):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
            return jax.random.gumbel(key, (v,), dtype=jnp.float32)

        g = jax.vmap(_gumbel)(seeds, positions)
        z = jnp.where(keep, ls + g, -jnp.inf)
        sampled = jnp.argmax(z, axis=-1)    # gumbel-max ~ softmax(keep)
        return jnp.where(temps > 0, sampled, greedy)

    # what only a sampling row needs (the vocabulary sort above all)
    # sits under a branch the device takes from ``temps``: a batch in
    # which no row samples costs the argmax and nothing else, and one
    # with a sampling row runs the same arithmetic on every row as a
    # straight line would
    return jax.lax.cond(jnp.any(temps > 0), _sampled,
                        lambda: greedy).astype(jnp.int64)
