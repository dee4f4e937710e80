"""Per-request decoding: sampling-as-data, logit processors, JSON grammar.

The serving engine compiles ONE decode step and ONE verify step per
geometry (the PR 9 unified step cache).  This module keeps it that way
while every request brings its own decoding recipe:

  - ``DecodeParams`` travels on the ``Request``; the engine batches the
    per-request fields into fixed-shape per-slot tensors — the ``samp``
    tuple ``(temperature[s], top_k[s], top_p[s], keys[s,2], mask[s,V])``
    — fed to the jitted steps as plain inputs, never compile keys.
    Greedy is temperature == 0: the step computes ``argmax`` on those
    rows bit-for-bit as before, so the PR 3..12 token-identity oracles
    survive unchanged while sampled/constrained/LoRA rows share the
    same executable in the same batch.
  - The logit-processor chain and the draws (a sort, two argsorts, a
    softmax, a cumsum and Gumbel noise over ``[rows, vocab]``) sit in
    the true branch of ONE ``lax.cond`` on "does any row sample"
    (``_where_any_sampled``), a device value read off the ``samp``
    input: a step whose rows are all greedy runs the argmax and the
    key split and nothing else, a batch with one sampled row computes
    what it always did for every row.  No flag, no second executable:
    the choice is made on the device, in the one compiled step.
  - Per-slot ``jax.random`` key state advances functionally inside the
    step (a fixed number of ``split``s per row per step, data
    independent), so a request's random stream depends only on its own
    seed — never on which slot, engine, or co-batched neighbors it got.
    That is what makes sampled output byte-identical across engine
    restarts and across symmetric-vs-disaggregated routing.
  - ``verify_tokens`` replaces the greedy prefix-match speculative
    verify with rejection sampling.  The n-gram drafter is
    deterministic (a delta proposal q), so the textbook accept rule
    collapses to: accept draft ``d`` with probability ``p(d)``; on
    rejection draw from ``p`` with ``d`` masked out (the normalized
    residual).  Either way the emitted token is an exact sample from
    ``p`` — speculative decoding matches the non-spec sampled
    distribution, and greedy rows keep the old prefix match exactly.
  - ``JsonGrammar`` compiles a character-level JSON pushdown over a
    token vocabulary.  The engine asks the per-request cursor for an
    additive ``[vocab]`` mask each step (0 = allowed, -1e9 = banned)
    and the budget-aware ``allowed`` filter only permits transitions
    whose minimal completion still fits in the request's remaining
    token budget — masked (greedy or sampled) output is valid JSON by
    construction for any ``max_new_tokens >= 1``.

Host-side classes here own no engine state; everything device-side is
pure jnp math imported lazily by ``models/generation.py`` inside the
jitted steps and reused eagerly for the prefill first token — offline
``generation.sample`` routes through the same primitives, so there is
exactly one source of sampling math in the tree.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "DecodeParams", "JsonGrammar", "NEG_MASK", "StopMatcher",
    "json_token_strings", "neutral_samp", "process_logits",
    "request_key", "sample_first", "sample_tokens", "split_keys",
    "verify_tokens",
]

# Additive-mask value for banned tokens.  Large enough that softmax
# underflows to exactly 0 in f32, small enough that dividing by any
# temperature the validator admits stays finite.
NEG_MASK = -1e9


# --------------------------------------------------------------------
# DecodeParams: the per-request recipe
# --------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DecodeParams:
    """Per-request decoding parameters, carried on ``Request``.

    temperature == 0 is greedy (the default — token-identical to the
    pre-sampling engine); temperature > 0 samples from the
    temperature-scaled, top-k/top-p-filtered distribution with a
    request-local PRNG stream seeded by ``seed``.  ``stop_sequences``
    are token-id suffixes checked host-side after every committed
    token (the stop tokens stay in the output).  ``json_mode`` asks
    the engine to constrain every token through its ``JsonGrammar``
    (engine-constructor argument) — incompatible with speculative
    decoding, which verifies several positions against one mask.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    stop_sequences: Tuple[Tuple[int, ...], ...] = ()
    seed: int = 0
    json_mode: bool = False

    def __post_init__(self):
        t = self.temperature
        if not (isinstance(t, (int, float)) and np.isfinite(t)) or t < 0:
            raise ValueError(
                f"temperature must be a finite float >= 0, got {t!r}")
        if not isinstance(self.top_k, int) or isinstance(self.top_k, bool) \
                or self.top_k < 0:
            raise ValueError(
                f"top_k must be an int >= 0 (0 disables), got "
                f"{self.top_k!r}")
        p = self.top_p
        if not (isinstance(p, (int, float)) and np.isfinite(p)) \
                or not (0.0 <= p <= 1.0):
            raise ValueError(
                f"top_p must be in [0, 1] (0 or 1 disables), got {p!r}")
        if not isinstance(self.seed, int) or isinstance(self.seed, bool):
            raise ValueError(f"seed must be an int, got {self.seed!r}")
        stops = []
        for s in self.stop_sequences:
            s = tuple(s)
            if not s or not all(isinstance(t, (int, np.integer))
                                for t in s):
                raise ValueError(
                    "stop_sequences must be non-empty sequences of "
                    f"token ids, got {s!r}")
            stops.append(tuple(int(t) for t in s))
        object.__setattr__(self, "stop_sequences", tuple(stops))
        if not isinstance(self.json_mode, bool):
            raise ValueError(
                f"json_mode must be a bool, got {self.json_mode!r}")

    @property
    def is_greedy(self) -> bool:
        return self.temperature == 0.0

    @property
    def is_default(self) -> bool:
        """True when the request needs none of the sampling machinery
        (plain greedy, no stops, no grammar) — the engine's fast path."""
        return (self.is_greedy and not self.stop_sequences
                and not self.json_mode)


@functools.lru_cache(maxsize=4096)
def _root_key(seed: int) -> Tuple[int, ...]:
    import jax
    return tuple(np.asarray(jax.random.PRNGKey(seed),
                            dtype=np.uint32).tolist())


def request_key(seed: int) -> np.ndarray:
    """The request-local PRNG root: a raw ``[2] uint32`` threefry key.

    Derived from the request's seed alone — never from slot index or
    engine identity — so restarts and re-routing replay the stream.
    ``jax.random.PRNGKey`` is a program on the device and its fetch
    waits behind whatever the device was given (the decode step in
    flight: a step's time in every ``submit``, with the scheduler
    standing still behind it), so a seed's key is computed once a
    process: every greedy request carries seed 0."""
    return np.array(_root_key(int(seed)), dtype=np.uint32)


def neutral_samp(rows: int, vocab: int):
    """Per-slot sampling inputs that reproduce pure greedy decoding.

    temperature 0 routes every row through the argmax of
    ``sample_tokens`` on bit-identical logits (the additive mask is
    exactly zero).  A batch of such rows alone takes the false branch
    of the step's ``lax.cond`` on "does any row sample", so offline
    greedy/beam callers and all-greedy engine batches pay for the
    argmax and the key split only, not for the processor chain or the
    draws; an empty engine slot beside a sampled row rides that row's
    branch, as every row of a mixed batch does."""
    return (np.zeros((rows,), np.float32),
            np.zeros((rows,), np.int32),
            np.zeros((rows,), np.float32),
            np.zeros((rows, 2), np.uint32),
            np.zeros((rows, vocab), np.float32))


# --------------------------------------------------------------------
# Device-side sampling math (pure jnp; traced into the jitted steps)
# --------------------------------------------------------------------

def process_logits(logits, temp, top_k, top_p):
    """Shared logit-processor chain: temperature, top-k, then top-p.

    ``logits`` is ``[rows, vocab]``; the three params are per-row
    vectors.  0 disables top-k; 0 or 1 disables top-p.  Rows with
    temp == 0 are scaled by 1 (the caller takes the argmax branch for
    them); filtered-out entries drop to ``NEG_MASK`` so softmax gives
    them exactly zero mass in f32.
    """
    import jax
    import jax.numpy as jnp
    neg = jnp.asarray(NEG_MASK, logits.dtype)
    v = logits.shape[-1]
    scale = jnp.where(temp > 0, temp, 1.0).astype(logits.dtype)
    lg = logits / scale[:, None]
    # top-k: keep the k highest logits per row
    kk = jnp.clip(top_k, 0, v)
    srt = jnp.sort(lg, axis=-1)                      # ascending
    kth = jnp.take_along_axis(
        srt, jnp.clip(v - kk, 0, v - 1)[:, None], axis=-1)
    lg = jnp.where((kk <= 0)[:, None] | (lg >= kth), lg, neg)
    # top-p (nucleus): smallest prob-sorted prefix reaching mass p.
    # Keep entries whose *exclusive* cumulative mass is < p — the
    # top-1 token always survives, even when p is tiny.
    active = ((top_p > 0) & (top_p < 1))[:, None]
    order = jnp.argsort(-lg, axis=-1)
    sorted_lg = jnp.take_along_axis(lg, order, axis=-1)
    probs = jax.nn.softmax(sorted_lg, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (csum - probs) < top_p[:, None]
    keep = jnp.take_along_axis(keep_sorted, jnp.argsort(order, axis=-1),
                               axis=-1)
    return jnp.where(active & ~keep, neg, lg)


def split_keys(keys):
    """Advance per-row keys one step: ``[rows, 2] -> (carry, sub)``.

    One vmapped split per row per step, unconditionally — the key
    schedule is data-independent, which is the determinism contract."""
    import jax
    pairs = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    return pairs[:, 0], pairs[:, 1]


def _where_any_sampled(temp, sampled, greedy):
    """``sampled()`` in steps where some row has temp > 0, else
    ``greedy`` (a pytree of the same shapes), under one ``lax.cond``.

    The predicate is a device value computed from the step's own
    ``samp`` input, so one executable serves both kinds of step and an
    all-greedy batch runs nothing that only ``sampled`` computes.  A
    mixed batch takes the true branch for every row; ``sampled`` keeps
    ``greedy`` for its temp == 0 rows."""
    import jax
    import jax.numpy as jnp
    return jax.lax.cond(jnp.any(temp > 0), sampled, lambda: greedy)


def sample_tokens(logits, samp):
    """One next token per row from ``[rows, vocab]`` logits.

    ``samp = (temperature, top_k, top_p, keys, mask)``.  Returns
    ``(tokens [rows] i32, carry_keys [rows, 2] uint32)``.  Greedy rows
    (temp == 0) take ``argmax(logits + mask)`` — with a zero mask this
    is bit-identical to the pre-sampling decode step.  The processor
    chain and the categorical draw run only in steps where some row
    samples (``_where_any_sampled``); the key split runs in every
    step, so the carry is the same in both branches and a request's
    stream depends on its seed alone.
    """
    import jax
    import jax.numpy as jnp
    temp, top_k, top_p, keys, mask = samp
    lgm = logits + mask
    greedy = jnp.argmax(lgm, axis=-1).astype(jnp.int32)
    carry, sub = split_keys(keys)

    def sampled():
        proc = process_logits(lgm, temp, top_k, top_p)
        drawn = jax.vmap(jax.random.categorical)(sub, proc)
        return jnp.where(temp > 0, drawn.astype(jnp.int32), greedy)

    return _where_any_sampled(temp, sampled, greedy), carry


def verify_tokens(logits, drafts, samp):
    """Rejection-sampled speculative verify over ``K+1`` positions.

    ``logits`` is ``[rows, K+1, vocab]`` (target scores at each draft
    position plus the bonus position), ``drafts`` is ``[rows, K]``.
    Returns ``(chosen [rows, K+1] i32, accept [rows, K] bool,
    carry_keys)``.  Position ``i``'s target law ``p_i`` is the softmax
    of the processed (masked/temperature/top-k/top-p) logits — exactly
    what non-speculative decode samples from.  The deterministic
    drafter makes the accept rule ``u_i < p_i(draft_i)`` and the
    rejection draw "``p_i`` with the draft masked out"; the bonus
    position is a plain sample from ``p_K``.  Greedy rows reduce to
    ``chosen = argmax`` and ``accept = (argmax == draft)`` — the PR 7
    prefix match, token-identical — and a step whose rows are all
    greedy computes that and the key split alone: the chain over
    ``rows x (K+1)`` positions, the softmax, the uniforms and the
    residual draws run only where some row samples
    (``_where_any_sampled``).  Entries past a row's first
    rejection are garbage by construction; the engine's host loop
    commits the accepted prefix and rolls the KV write offset back.
    """
    import jax
    import jax.numpy as jnp
    temp, top_k, top_p, keys, mask = samp
    rows, kp1, vocab = logits.shape
    k = kp1 - 1
    lgm = logits + mask[:, None, :]
    greedy = jnp.argmax(lgm, axis=-1).astype(jnp.int32)
    greedy_accept = greedy[:, :k] == drafts
    carry, sub = split_keys(keys)

    def sampled():
        neg = jnp.asarray(NEG_MASK, logits.dtype)
        rep = lambda x: jnp.repeat(x, kp1)
        proc = process_logits(
            lgm.reshape(rows * kp1, vocab), rep(temp), rep(top_k),
            rep(top_p)).reshape(rows, kp1, vocab)
        # Fixed fan-out per row per step: K+1 accept draws + K+1 token
        # draws, consumed whether or not any draft survives.
        subs = jax.vmap(lambda kk: jax.random.split(kk, 2 * kp1))(sub)
        ukeys, ckeys = subs[:, :kp1], subs[:, kp1:]
        bonus = jax.vmap(jax.random.categorical)(
            ckeys[:, k], proc[:, k]).astype(jnp.int32)
        samples = (temp > 0)[:, None]
        if k == 0:
            return (jnp.where(samples, bonus[:, None], greedy),
                    greedy_accept)
        probs = jax.nn.softmax(proc, axis=-1)
        draft_p = jnp.take_along_axis(
            probs[:, :k], drafts[..., None].astype(jnp.int32),
            axis=-1)[..., 0]
        u = jax.vmap(jax.vmap(jax.random.uniform))(ukeys[:, :k])
        accept_s = u < draft_p
        resid = jnp.where(jax.nn.one_hot(drafts, vocab, dtype=bool),
                          neg, proc[:, :k])
        resample = jax.vmap(jax.vmap(jax.random.categorical))(
            ckeys[:, :k], resid).astype(jnp.int32)
        chosen_s = jnp.where(accept_s, drafts.astype(jnp.int32), resample)
        chosen_s = jnp.concatenate([chosen_s, bonus[:, None]], axis=1)
        return (jnp.where(samples, chosen_s, greedy),
                jnp.where(samples, accept_s, greedy_accept))

    chosen, accept = _where_any_sampled(temp, sampled,
                                        (greedy, greedy_accept))
    return chosen, accept, carry


def sample_first(logits_row, params: DecodeParams, key: np.ndarray,
                 mask_row=None):
    """Host-side first-token draw from one prefill logits row.

    Prefill signatures stay untouched (and untraced by this): the
    engine samples the first token eagerly through the *same*
    ``sample_tokens`` math the jitted steps trace, with the request's
    own key.  Returns ``(token, carry_key)``."""
    import jax.numpy as jnp
    lg = jnp.asarray(logits_row, jnp.float32)[None, :]
    v = lg.shape[-1]
    mask = (jnp.zeros((1, v), jnp.float32) if mask_row is None
            else jnp.asarray(mask_row, jnp.float32)[None, :])
    samp = (jnp.full((1,), params.temperature, jnp.float32),
            jnp.full((1,), params.top_k, jnp.int32),
            jnp.full((1,), params.top_p, jnp.float32),
            jnp.asarray(key, jnp.uint32)[None, :],
            mask)
    tok, carry = sample_tokens(lg, samp)
    return int(tok[0]), np.asarray(carry[0], np.uint32)


# --------------------------------------------------------------------
# Stop sequences: incremental KMP matching on the host
# --------------------------------------------------------------------
#
# Stop matching used to be a naive suffix scan over the whole generated
# tail after every committed token — O(len^2) per request over its
# lifetime.  The matcher below is the classic KMP automaton: the
# failure function, whose state after feeding tokens t_1..t_k is the
# length of the longest prefix of the pattern that is a suffix of the
# fed stream.  state == len(pattern) therefore holds exactly when
# ``t[-len(s):] == list(s)`` — the old check, token for token — but
# each ``feed`` is O(1) amortized.


def _kmp_fail(pat):
    """KMP failure function as a length ``m+1`` table: ``fail[s]`` is
    the longest proper prefix of ``pat[:s]`` that is also its suffix
    (``fail[0] = fail[1] = 0``).  ``fail[s] < s`` for s >= 1, so the
    fail-chase of :meth:`StopMatcher.feed` ends."""
    m = len(pat)
    fail = [0] * (m + 1)
    k = 0
    for i in range(1, m):
        while k > 0 and pat[i] != pat[k]:
            k = fail[k]
        if pat[i] == pat[k]:
            k += 1
        fail[i + 1] = k
    return fail


class StopMatcher:
    """Incremental host-side stop-sequence matcher for one request.

    One KMP automaton per stop pattern; ``feed(token)`` advances all of
    them in O(total pattern length) worst case, O(1) amortized, and
    latches ``hit`` on the first match.  A request re-homed onto
    another engine rebuilds its state by replaying its committed
    tokens (``feed_all``)."""

    __slots__ = ("patterns", "fails", "states", "hit")

    def __init__(self, stop_sequences: Sequence[Sequence[int]]):
        self.patterns = [tuple(int(t) for t in s) for s in stop_sequences]
        if any(not p for p in self.patterns):
            raise ValueError("stop sequences must be non-empty")
        self.fails = [_kmp_fail(p) for p in self.patterns]
        self.states = [0] * len(self.patterns)
        self.hit = False

    def feed(self, token: int) -> bool:
        """Advance every automaton over one committed token; returns
        (and latches) whether any stop sequence has now matched."""
        tok = int(token)
        for j, pat in enumerate(self.patterns):
            s = self.states[j]
            fail = self.fails[j]
            while s > 0 and (s >= len(pat) or pat[s] != tok):
                s = fail[s]
            s = s + 1 if pat[s] == tok else 0
            self.states[j] = s
            if s == len(pat):
                self.hit = True
        return self.hit

    def feed_all(self, tokens: Sequence[int]) -> bool:
        for t in tokens:
            self.feed(t)
        return self.hit


# --------------------------------------------------------------------
# JSON-grammar constrained decoding (host-side pushdown -> mask rows)
# --------------------------------------------------------------------

_DIGITS = "0123456789"
_STRING_CHARS = "abcdefghijklmnopqrstuvwxyz0123456789_ "
_VALUE_STARTS = '"-{[' + _DIGITS
_ALPHABET = '{}[]:,"-.' + _DIGITS + "abcdefghijklmnopqrstuvwxyz_ "


def json_token_strings(vocab_size: int):
    """A canonical id -> char table covering the JSON alphabet.

    The repo has no tokenizer, so constrained decoding is defined over
    an explicit per-id string table.  Token 0 stays unmapped (it backs
    engine padding); ids 1.. cycle through the grammar alphabet so any
    vocab with more than ``len(alphabet)`` ids can express every JSON
    construct.  Real deployments pass their tokenizer's own table —
    any id mapping to something other than a single known char is
    simply always banned."""
    if vocab_size <= len(_ALPHABET):
        raise ValueError(
            f"vocab_size {vocab_size} cannot cover the "
            f"{len(_ALPHABET)}-char JSON alphabet")
    return [""] + [_ALPHABET[(i - 1) % len(_ALPHABET)]
                   for i in range(1, vocab_size)]


class JsonGrammar:
    """A JSON grammar compiled over a token vocabulary.

    The grammar is a restricted-but-valid JSON subset: objects,
    arrays, escape-free lowercase strings, and decimal numbers.
    ``start()`` returns a per-request :class:`JsonCursor`; the engine
    advances it on every committed token and reads a fresh additive
    mask row per step."""

    def __init__(self, token_strings: Sequence[Optional[str]]):
        self.token_strings = list(token_strings)
        self.vocab_size = len(self.token_strings)
        self._by_char = {}
        for tid, s in enumerate(self.token_strings):
            if isinstance(s, str) and len(s) == 1:
                self._by_char.setdefault(s, []).append(tid)
        missing = [c for c in _ALPHABET if c not in self._by_char]
        if missing:
            raise ValueError(
                f"token table cannot express JSON: no token maps to "
                f"{missing!r}")
        self._char_ids = {c: np.asarray(ids, np.int64)
                          for c, ids in self._by_char.items()}

    def start(self) -> "JsonCursor":
        return JsonCursor(self)

    def decode(self, token_ids: Sequence[int]) -> str:
        """The emitted text for a token-id sequence (tests feed this
        straight into ``json.loads``)."""
        return "".join(self.token_strings[t] or "" for t in token_ids)


class JsonCursor:
    """Pushdown state for one constrained request.

    States: ``value`` (expecting a value), ``string``/``key`` (inside
    a string), ``colon``, ``num_sign``/``num_int``/``num_frac0``/
    ``num_frac``, ``obj_first``/``obj_key``/``obj_next``,
    ``arr_first``/``arr_next``, ``end``.  The stack holds one closing
    char per open container.  Numbers are self-terminating: a
    separator/closer char first pops the number, then re-dispatches.
    """

    __slots__ = ("_g", "_stack", "_state")

    def __init__(self, grammar: JsonGrammar):
        self._g = grammar
        self._stack = []
        self._state = "value"

    # -- transition relation ------------------------------------------

    def _pop_value(self):
        if not self._stack:
            self._state = "end"
        else:
            self._state = ("obj_next" if self._stack[-1] == "}"
                           else "arr_next")

    def _advance_char(self, ch: str):
        st = self._state
        if st == "value" or st == "arr_first":
            if st == "arr_first" and ch == "]":
                self._stack.pop()
                self._pop_value()
            elif ch == '"':
                self._state = "string"
            elif ch == "-":
                self._state = "num_sign"
            elif ch in _DIGITS:
                self._state = "num_int"
            elif ch == "{":
                self._stack.append("}")
                self._state = "obj_first"
            elif ch == "[":
                self._stack.append("]")
                self._state = "arr_first"
            else:
                raise ValueError(f"char {ch!r} invalid in state {st}")
        elif st in ("string", "key"):
            if ch == '"':
                if st == "key":
                    self._state = "colon"
                else:
                    self._pop_value()
            elif ch in _STRING_CHARS:
                pass
            else:
                raise ValueError(f"char {ch!r} invalid in a string")
        elif st == "colon":
            if ch != ":":
                raise ValueError(f"expected ':', got {ch!r}")
            self._state = "value"
        elif st == "num_sign":
            if ch not in _DIGITS:
                raise ValueError(f"expected digit after '-', got {ch!r}")
            self._state = "num_int"
        elif st == "num_frac0":
            if ch not in _DIGITS:
                raise ValueError(f"expected digit after '.', got {ch!r}")
            self._state = "num_frac"
        elif st in ("num_int", "num_frac"):
            if ch in _DIGITS:
                pass
            elif ch == "." and st == "num_int":
                self._state = "num_frac0"
            else:
                self._pop_value()
                self._advance_char(ch)
        elif st == "obj_first":
            if ch == '"':
                self._state = "key"
            elif ch == "}":
                self._stack.pop()
                self._pop_value()
            else:
                raise ValueError(f"char {ch!r} invalid after '{{'")
        elif st == "obj_key":
            if ch != '"':
                raise ValueError(f"expected '\"', got {ch!r}")
            self._state = "key"
        elif st == "obj_next":
            if ch == ",":
                self._state = "obj_key"
            elif ch == "}":
                self._stack.pop()
                self._pop_value()
            else:
                raise ValueError(f"char {ch!r} invalid after a member")
        elif st == "arr_next":
            if ch == ",":
                self._state = "value"
            elif ch == "]":
                self._stack.pop()
                self._pop_value()
            else:
                raise ValueError(f"char {ch!r} invalid after an element")
        else:  # end
            raise ValueError("document already complete")

    def _candidate_chars(self) -> str:
        st, stack = self._state, self._stack
        term = "" if not stack else "," + stack[-1]
        if st == "value":
            return _VALUE_STARTS
        if st in ("string", "key"):
            return _STRING_CHARS + '"'
        if st == "colon":
            return ":"
        if st in ("num_sign", "num_frac0"):
            return _DIGITS
        if st == "num_int":
            return _DIGITS + "." + term
        if st == "num_frac":
            return _DIGITS + term
        if st == "obj_first":
            return '"}'
        if st == "obj_key":
            return '"'
        if st == "obj_next":
            return ",}"
        if st == "arr_first":
            return _VALUE_STARTS + "]"
        if st == "arr_next":
            return ",]"
        return ""  # end

    def _min_remaining(self) -> int:
        """Fewest further chars to reach an accepting configuration."""
        depth = len(self._stack)
        return depth + {
            "value": 1, "string": 1, "key": 3, "colon": 2,
            "num_sign": 1, "num_frac0": 1, "num_int": 0, "num_frac": 0,
            "obj_first": 0, "obj_key": 4, "obj_next": 0,
            "arr_first": 0, "arr_next": 0, "end": 0,
        }[self._state]

    # -- public surface -----------------------------------------------

    @property
    def at_end(self) -> bool:
        return self._state == "end"

    @property
    def accepting(self) -> bool:
        """True when the emitted prefix is complete valid JSON."""
        return (self._state == "end"
                or (not self._stack
                    and self._state in ("num_int", "num_frac")))

    def advance(self, token_id: int):
        s = self._g.token_strings[int(token_id)]
        if not isinstance(s, str) or len(s) != 1:
            raise ValueError(
                f"token {token_id} maps to {s!r}, not a grammar char")
        self._advance_char(s)

    def allowed_chars(self, remaining: int) -> str:
        """Chars legal now AND completable within ``remaining`` tokens.

        Filtering on the minimal completion of the post-transition
        configuration is what makes the valid-by-construction claim
        hold for any budget: the engine's invariant
        ``min_remaining() <= remaining`` is preserved by every allowed
        transition, so budget exhaustion always lands accepting."""
        out = []
        for ch in self._candidate_chars():
            probe = JsonCursor(self._g)
            probe._stack = list(self._stack)
            probe._state = self._state
            probe._advance_char(ch)
            if probe._min_remaining() <= remaining - 1:
                out.append(ch)
        return "".join(out)

    def mask_row(self, remaining: int, out: Optional[np.ndarray] = None
                 ) -> np.ndarray:
        """The additive ``[vocab]`` f32 mask row for the next token."""
        if out is None:
            out = np.empty((self._g.vocab_size,), np.float32)
        out.fill(NEG_MASK)
        for ch in self.allowed_chars(remaining):
            out[self._g._char_ids[ch]] = 0.0
        return out
