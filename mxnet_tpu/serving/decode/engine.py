"""DecodePredictor: KV-cached autoregressive serving programs + slots.

The Predictor freezes one symbol into per-bucket one-shot programs;
this engine freezes a ``TransformerLMSpec`` weight set into the TWO
program families iterative decode needs (model.py):

- one PREFILL program per prompt-length bucket — batch-1 per request,
  fills the request's slot rows of the KV-cache, emits token #1. Every
  admission runs the identical program whether the server is idle or
  saturated, which is half of the bit-identity guarantee;
- ONE DECODE program — advances all ``slots`` lanes a single token
  against the cache. Lanes are data-independent, so a lane's output
  doesn't depend on which other slots are occupied: the other half;
- per-width VERIFY programs (round 21, ``verify_widths``) — advance
  all lanes up to K tokens in one launch for speculative decoding
  (model.verify_step); width is compile-key material like the prefill
  buckets, and warmup materializes every declared width so serving
  performs zero fresh verify traces.

The KV-cache is DONATED device state: ``2 * num_layers`` buffers of
``(slots, max_seq, heads, head_dim)`` float32 — or, under
``MXTPU_DECODE_KV_DTYPE=int8``, ``4 * num_layers`` int8 value +
per-row f32 scale buffers (model.py, round 19) — threaded through
every call (donated back to XLA where the backend supports donation —
``compile.donation_supported()``), never copied to host. Cache layout
AND dtype, ``max_seq`` and ``slots`` are compile-key material, and the
accounted cache footprint is recorded in ``mx.memory_report()`` next
to the per-program peaks so cache sizing is driven by measured HBM
headroom — under int8 the decode_state row drops to ~0.31× f32, which
is the "roughly double the slots per chip" capacity lever.

Programs go through the r10 compile registry (``load_or_compile`` +
``note_entry_point``): AOT persistent-cache warm starts, retrace
guards, and ``compile_report()`` pinning — a full serving run performs
zero fresh compiles beyond the per-bucket prefill programs plus the one
decode program (tests pin this).

The slot allocator lives here (under the engine lock): ``prefill`` into
a free slot, per-slot positions advance per ``decode`` call, ``release``
returns the slot for mid-flight backfill. ``generate()`` is the solo
streaming surface over the same programs — also the oracle the
continuous-batching drill compares against.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from ... import config
from ...base import MXNetError
from . import model as _model
from .. import _register_decoder

__all__ = ["DecodePredictor", "default_seq_buckets"]


def default_seq_buckets(max_seq):
    """Prompt-length buckets from MXTPU_DECODE_SEQ_BUCKETS, clipped to
    ``max_seq`` (which is always a bucket: any prompt the spec admits
    has a program)."""
    raw = str(config.get("MXTPU_DECODE_SEQ_BUCKETS", "16,64"))
    try:
        buckets = sorted({int(x) for x in raw.replace(" ", "").split(",")
                          if x})
    except ValueError:
        raise MXNetError(
            f"MXTPU_DECODE_SEQ_BUCKETS={raw!r} is not a comma-separated "
            "integer list")
    if buckets and buckets[0] < 1:
        raise MXNetError(
            f"MXTPU_DECODE_SEQ_BUCKETS={raw!r} must name positive "
            "prompt lengths")
    buckets = [b for b in buckets if b <= max_seq]
    if not buckets or buckets[-1] != max_seq:
        buckets.append(max_seq)
    return tuple(buckets)


class DecodePredictor:
    """KV-cached decode serving over a frozen transformer LM.

    Parameters
    ----------
    spec : TransformerLMSpec
    params : dict name -> array/NDArray
        Trained weights matching ``spec.param_shapes()`` (e.g.
        ``Module.get_params()[0]`` of the ``build_symbol`` graph).
    slots : int, optional
        Concurrent generation lanes (default MXTPU_DECODE_SLOTS).
    seq_buckets : tuple of int, optional
        Prompt-length buckets (default MXTPU_DECODE_SEQ_BUCKETS,
        clipped to ``spec.max_seq`` which is always included).
    name : str, optional
        Label for programs/telemetry (default ``spec.name``).
    kv_dtype : str, optional
        Cache storage dtype, ``"float32"`` or ``"int8"`` (default
        ``MXTPU_DECODE_KV_DTYPE``). int8 stores per-row absmax scales
        and dequantizes at f32 compute (model.py); the layout is
        compile-key material, so flipping it is a program miss.
    """

    def __init__(self, spec, params, slots=None, seq_buckets=None,
                 name=None, kv_dtype=None):
        import jax
        import jax.numpy as jnp
        from ... import compile as compile_mod

        self.spec = spec
        self.name = name or spec.name
        self.slots = int(slots) if slots is not None \
            else int(config.get("MXTPU_DECODE_SLOTS", 4))
        if self.slots < 1:
            raise MXNetError(f"slots={self.slots} must be >= 1")
        self.kv_dtype = _model.check_kv_dtype(
            kv_dtype if kv_dtype is not None
            else config.get("MXTPU_DECODE_KV_DTYPE", "float32"))
        self.buckets = tuple(sorted(set(
            int(b) for b in seq_buckets))) if seq_buckets \
            else default_seq_buckets(spec.max_seq)
        if self.buckets[-1] > spec.max_seq:
            raise MXNetError(
                f"seq bucket {self.buckets[-1]} exceeds "
                f"spec.max_seq={spec.max_seq}")

        shapes = spec.param_shapes()
        missing = [n for n in shapes if n not in params]
        if missing:
            raise MXNetError(f"DecodePredictor missing params {missing}")
        pvals = {}
        for n, want in shapes.items():
            a = np.asarray(getattr(params[n], "_data",
                                   getattr(params[n], "data", params[n])),
                           dtype=np.float32)
            if tuple(a.shape) != tuple(want):
                raise MXNetError(
                    f"param '{n}' has shape {a.shape}, spec wants "
                    f"{tuple(want)}")
            pvals[n] = jax.device_put(jnp.asarray(a))
        self._pnames = spec.param_names()
        self._pvals_t = tuple(pvals[n] for n in self._pnames)

        self._caches = tuple(
            jax.device_put(c) for c in _model.init_caches(
                spec, self.slots, kv_dtype=self.kv_dtype))

        pnames = list(self._pnames)
        kv_dtype_s = self.kv_dtype

        def prefill_fn(pvals_t, caches, tokens, length, slot):
            p = dict(zip(pnames, pvals_t))
            return _model.prefill_step(spec, p, caches, tokens, length,
                                       slot, kv_dtype=kv_dtype_s)

        def decode_fn(pvals_t, caches, tokens, positions, active):
            p = dict(zip(pnames, pvals_t))
            return _model.decode_step(spec, p, caches, tokens,
                                      positions, active,
                                      kv_dtype=kv_dtype_s)

        def verify_fn(pvals_t, caches, tokens, positions, n_tokens,
                      active):
            p = dict(zip(pnames, pvals_t))
            return _model.verify_step(spec, p, caches, tokens,
                                      positions, n_tokens, active,
                                      kv_dtype=kv_dtype_s)

        def reprefill_fn(pvals_t, tokens, length):
            p = dict(zip(pnames, pvals_t))
            return _model.reprefill_step(spec, p, tokens, length)

        donate = {"donate_argnums": (1,)} \
            if compile_mod.donation_supported() else {}
        self._donate = bool(donate)
        self._prefill_jit = jax.jit(prefill_fn, **donate)
        self._decode_jit = jax.jit(decode_fn, **donate)
        self._verify_jit = jax.jit(verify_fn, **donate)
        self._reprefill_jit = jax.jit(reprefill_fn)
        # multi-token verify widths warmup should materialize; empty on
        # a plain engine (verify still compiles lazily at any width the
        # caller asks for), set by SpecDecodePredictor to (k+1,)
        self.verify_widths = ()

        self._lock = threading.RLock()
        self._programs = {}       # ("prefill", b) / ("decode",) / ...
        self._program_costs = {}
        self._program_memory = {}
        self._materialized = 0
        self._cache_loads = 0
        self._free = list(range(self.slots))      # LIFO slot allocator
        self._slot_pos = [0] * self.slots         # next write position
        self._decode_steps = 0
        self._verify_steps = 0
        self._prefills = 0
        self._tokens = 0

        _register_decoder(self)
        from ...telemetry import registry as treg
        self._tokens_c = treg.counter(
            f"serving::{self.telemetry_id}::tokens")
        treg.gauge(f"serving::{self.telemetry_id}::kv_cache_bytes").set(
            self.kv_cache_bytes())
        # the cache is persistent device STATE, not a per-program temp:
        # give it its own memory_report() row so HBM headroom math sees
        # it next to the program peaks
        from ...telemetry import memory as _tmem
        kv = self.kv_cache_bytes()
        _tmem.record(
            f"decode:{self.telemetry_id}:kv_cache", "decode_state",
            f"kv:{self.telemetry_id}",
            {"argument_bytes": kv, "output_bytes": kv,
             "alias_bytes": kv, "peak_bytes": kv,
             "donation_saved_bytes": kv if self._donate else 0})

    # -- construction helpers -------------------------------------------------
    @classmethod
    def from_module(cls, module, spec, **kwargs):
        """Freeze a trained (bound+initialized) Module of the
        ``build_symbol(spec, ...)`` graph — param names ARE the
        contract, no translation layer."""
        arg_params, _aux = module.get_params()
        return cls(spec, arg_params, **kwargs)

    # -- bucketing / capacity -------------------------------------------------
    @property
    def max_batch(self):
        """Decode lanes (the DecodeBatcher's concurrency bound)."""
        return self.slots

    @property
    def retraces(self):
        return self._materialized

    def bucket_for(self, n):
        for b in self.buckets:
            if n <= b:
                return b
        raise MXNetError(
            f"prompt of {n} tokens exceeds the largest seq bucket "
            f"({self.buckets[-1]})")

    def gen_limit(self, prompt_len, max_new_tokens=None):
        """Max tokens producible for a prompt: the cache holds positions
        ``[0, max_seq)`` so generation is capped at
        ``max_seq - prompt_len + 1`` (token #1 costs no cache row; each
        further token writes one). Solo ``generate`` and the batcher
        clamp through HERE — identical limits are part of bit-identity.
        """
        cap = self.spec.max_seq - prompt_len + 1
        if max_new_tokens is None:
            return cap
        return max(1, min(int(max_new_tokens), cap))

    def check_prompt(self, prompt):
        """Validate/convert one prompt to a 1-D int32 numpy array."""
        a = np.asarray(getattr(prompt, "_data", prompt))
        if a.ndim != 1 or a.shape[0] < 1:
            raise MXNetError(
                f"prompt must be a non-empty 1-D token sequence, got "
                f"shape {tuple(a.shape)}")
        if a.shape[0] > self.spec.max_seq:
            raise MXNetError(
                f"prompt of {a.shape[0]} tokens exceeds "
                f"max_seq={self.spec.max_seq}")
        return a.astype(np.int32)

    # -- compile registry -----------------------------------------------------
    def _program_key(self, kind, bucket=None):
        from ... import compile as compile_mod
        extra = dict(self.spec.key_material())
        layout = ("slot-major:int8+f32scale" if self.kv_dtype == "int8"
                  else "slot-major:f32")
        extra.update({
            "slots": self.slots,
            "cache_layout": layout if kind != "reprefill" else "none",
            "donate": self._donate and kind != "reprefill",
        })
        if kind == "verify":
            # ``bucket`` is the verify WIDTH (max fed tokens per lane):
            # width is program-shape material exactly like a prefill's
            # seq bucket, so each width is its own registry entry
            sigs = (("tokens", (self.slots, bucket), "int32"),)
            label = f"decode:{self.name}:verify:k{bucket}"
        else:
            sigs = ((("tokens", (1, bucket), "int32"),)
                    if bucket is not None
                    else (("tokens", (self.slots,), "int32"),))
            label = f"decode:{self.name}:{kind}" + \
                (f":s{bucket}" if bucket is not None else "")
        return compile_mod.program_key(
            "decode", label, input_sigs=sigs, extra=extra)

    def _acquire(self, pkey_id, kind, bucket, jit_fn, args):
        """Acquire one compiled program through the compile registry
        (AOT cache, retrace guard), mirroring
        Predictor._acquire_program: cache-entry failures are the
        registry's to absorb, a trace or compile error surfaces."""
        from ... import compile as compile_mod
        key = self._program_key(kind, bucket)
        exe, source = compile_mod.load_or_compile(
            key, lambda: jit_fn.lower(*args))
        compile_mod.note_entry_point(
            key.name, key, compile_mod.arg_signature(args[1]))
        self._note_cost(pkey_id, key, exe)
        if source == "cache":
            self._cache_loads += 1

            def _reject():
                self._programs[pkey_id] = jit_fn
                self._materialized += 1
            return compile_mod.guarded_loaded_program(
                exe, jit_fn, "decode", on_reject=_reject)
        self._materialized += 1
        return exe

    def _note_cost(self, pkey_id, key, exe):
        try:
            cost = exe.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            self._program_costs[pkey_id] = dict(cost) if cost else {}
        except Exception:
            self._program_costs[pkey_id] = {}
        try:
            from ...telemetry import memory as _tmem
            self._program_memory[pkey_id] = _tmem.analyze(exe)
            _tmem.record(f"decode:{self.telemetry_id}:" +
                         ":".join(str(x) for x in pkey_id), "decode",
                         key.digest, exe)
        except Exception:
            self._program_memory[pkey_id] = {}

    def _run(self, pkey_id, kind, bucket, jit_fn, args):
        fn = self._programs.get(pkey_id)
        if fn is None:
            fn = self._acquire(pkey_id, kind, bucket, jit_fn, args)
            self._programs[pkey_id] = fn
        return fn(*args)

    # -- slot allocator (call under self._lock) -------------------------------
    def alloc_slot(self):
        """Claim a free decode lane, or None when saturated (the
        batcher's signal to leave work queued)."""
        with self._lock:
            return self._free.pop() if self._free else None

    def release(self, slot):
        """Return a lane to the pool (stale cache rows need no scrub:
        the next prefill overwrites its rows and attention masks beyond
        the live position with an exact-zero contribution)."""
        with self._lock:
            if slot not in self._free:
                self._free.append(slot)

    @property
    def free_slots(self):
        with self._lock:
            return len(self._free)

    def slot_pos(self, slot):
        """A lane's committed write position (next row index)."""
        with self._lock:
            return self._slot_pos[slot]

    def seek_slot(self, slot, pos):
        """Set a lane's committed position explicitly. The speculative
        layer commits an accepted prefix through here (``verify`` never
        advances positions itself — rows written for rejected drafts
        simply go stale behind the new position), and a KV-lane import
        lands its transferred position the same way."""
        if not 0 <= int(pos) <= self.spec.max_seq:
            raise MXNetError(
                f"seek_slot position {pos} outside [0, "
                f"{self.spec.max_seq}]")
        with self._lock:
            self._slot_pos[slot] = int(pos)

    # -- execution ------------------------------------------------------------
    def prefill(self, slot, prompt):
        """Fill ``slot`` from a validated prompt; returns token #1."""
        prompt = self.check_prompt(prompt)
        plen = prompt.shape[0]
        bucket = self.bucket_for(plen)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :plen] = prompt
        with self._lock:
            args = (self._pvals_t, self._caches, padded,
                    np.int32(plen), np.int32(slot))
            new_caches, nxt = self._run(
                ("prefill", bucket), "prefill", bucket,
                self._prefill_jit, args)
            self._caches = tuple(new_caches)
            self._slot_pos[slot] = plen
            self._prefills += 1
            self._tokens += 1
        self._tokens_c.inc()
        return int(nxt)

    def decode(self, slot_tokens):
        """One decode step: ``{slot: previous_token}`` for every active
        lane -> ``{slot: next_token}``. Consults the ``decode_step``
        fault site (1-based ``token`` ordinal) BEFORE touching device
        state, so an injected raise/kill leaves the cache un-advanced.
        """
        if not slot_tokens:
            return {}
        from ... import faultinject
        with self._lock:
            ordinal = self._decode_steps + 1
            if faultinject.fire("decode_step", token=ordinal):
                armed = faultinject.active("decode_step") or {}
                if armed.get("action") != "sleep":
                    raise faultinject.FaultInjected("decode_step",
                                                    token=ordinal)
                # sleep-armed: the slow-decode straggler drill (mirrors
                # replica_drop's sleep semantics) — fire() already
                # stretched this step, the program still runs
            tokens = np.zeros(self.slots, np.int32)
            positions = np.zeros(self.slots, np.int32)
            active = np.zeros(self.slots, bool)
            for slot, tok in slot_tokens.items():
                tokens[slot] = tok
                positions[slot] = self._slot_pos[slot]
                active[slot] = True
            args = (self._pvals_t, self._caches, tokens, positions,
                    active)
            new_caches, nxt = self._run(
                ("decode",), "decode", None, self._decode_jit, args)
            self._caches = tuple(new_caches)
            nxt = np.asarray(nxt)
            self._decode_steps += 1
            for slot in slot_tokens:
                self._slot_pos[slot] += 1
            self._tokens += len(slot_tokens)
        self._tokens_c.inc(len(slot_tokens))
        return {slot: int(nxt[slot]) for slot in slot_tokens}

    def _verify_width_for(self, n):
        for w in self.verify_widths:
            if n <= w:
                return w
        return n

    def verify(self, slot_feed):
        """One multi-token verify step: ``{slot: fed_tokens}`` — each
        lane's fed list is its last COMMITTED token followed by draft
        proposals — to ``{slot: np.int32 array}`` of the target's
        argmax after each fed token. Pads every lane to the smallest
        declared ``verify_widths`` bucket that fits (padding writes
        nowhere). Positions are NOT advanced: the caller decides the
        accepted prefix and commits it via ``seek_slot`` — which is
        what keeps a rejected draft's cache rows harmlessly stale
        instead of corrupting the lane."""
        if not slot_feed:
            return {}
        counts = {s: len(f) for s, f in slot_feed.items()}
        if min(counts.values()) < 1:
            raise MXNetError("verify needs at least the committed "
                             "token per lane")
        width = self._verify_width_for(max(counts.values()))
        with self._lock:
            tokens = np.zeros((self.slots, width), np.int32)
            positions = np.zeros(self.slots, np.int32)
            n_tok = np.ones(self.slots, np.int32)
            active = np.zeros(self.slots, bool)
            for slot, fed in slot_feed.items():
                n = counts[slot]
                tokens[slot, :n] = fed
                positions[slot] = self._slot_pos[slot]
                n_tok[slot] = n
                active[slot] = True
            args = (self._pvals_t, self._caches, tokens, positions,
                    n_tok, active)
            new_caches, outs = self._run(
                ("verify", width), "verify", width, self._verify_jit,
                args)
            self._caches = tuple(new_caches)
            outs = np.asarray(outs)
            self._verify_steps += 1
        return {slot: outs[slot, :counts[slot]].copy()
                for slot in slot_feed}

    # -- KV-lane handoff (disaggregated prefill/decode, round 21) -------------
    def lane_fingerprint(self):
        """Layout key a lane must match to transfer between engines:
        spec material + cache layout. Slots COUNT is deliberately not
        part of it — a prefill replica with 4 lanes hands off to a
        decode replica with 16."""
        layout = ("slot-major:int8+f32scale" if self.kv_dtype == "int8"
                  else "slot-major:f32")
        return dict(self.spec.key_material(), cache_layout=layout)

    def export_lane(self, slot):
        """Snapshot one lane's cache rows + committed position as a
        host-transportable dict — the prefill side of the KV-lane
        handoff. Under int8 KV the rows are the QUANTIZED buffers, so
        the handoff moves ~0.31× the f32 bytes (the r19 capacity lever
        doubling as a transfer-bytes lever)."""
        with self._lock:
            rows = [np.asarray(c[slot]) for c in self._caches]
            pos = self._slot_pos[slot]
        return {
            "fingerprint": self.lane_fingerprint(),
            "pos": int(pos),
            "rows": rows,
            "bytes": int(sum(r.nbytes for r in rows)),
        }

    def import_lane(self, slot, lane, prompt=None):
        """Land an exported lane into a free local slot — the decode
        side of the handoff. Refuses a fingerprint mismatch (two specs
        or two cache layouts must never silently mix rows). ``prompt``
        (the lane's committed tokens) is unused here but part of the
        contract: subclasses with auxiliary per-lane state — the
        speculative predictor's DRAFT cache — rebuild it from the
        prompt on import."""
        if lane["fingerprint"] != self.lane_fingerprint():
            raise MXNetError(
                f"KV-lane fingerprint mismatch: exporter "
                f"{lane['fingerprint']} vs importer "
                f"{self.lane_fingerprint()} — handoff requires "
                "identical spec + cache layout")
        import jax.numpy as jnp
        rows = lane["rows"]
        with self._lock:
            if len(rows) != len(self._caches):
                raise MXNetError(
                    f"KV-lane has {len(rows)} buffers, cache has "
                    f"{len(self._caches)}")
            self._caches = tuple(
                c.at[slot].set(jnp.asarray(r))
                for c, r in zip(self._caches, rows))
            self._slot_pos[slot] = int(lane["pos"])

    def generate(self, prompt, max_new_tokens=None, stop_token=None):
        """Stream tokens for ONE prompt (a generator): the solo surface
        over the same slot allocator and compiled programs the
        continuous batcher drives — which is why batched streams can be
        (and are, tests pin it) bit-identical to this.

        Yields ints; includes ``stop_token`` (generation halts after
        yielding it). Stops at ``max_new_tokens`` or when the cache is
        full (``gen_limit``)."""
        prompt = self.check_prompt(prompt)
        limit = self.gen_limit(prompt.shape[0], max_new_tokens)
        slot = self.alloc_slot()
        if slot is None:
            raise MXNetError(
                f"no free decode slot ({self.slots} busy); generate() "
                "is the solo surface — use DecodeBatcher for "
                "concurrent load")
        try:
            tok = self.prefill(slot, prompt)
            produced = 1
            yield tok
            while produced < limit and \
                    (stop_token is None or tok != stop_token):
                tok = self.decode({slot: tok})[slot]
                produced += 1
                yield tok
        finally:
            self.release(slot)

    def warmup(self):
        """Materialize every program (per-bucket prefill + the decode
        step) before live traffic; slot 0's scratch writes are harmless
        (release() doc). Returns the fresh-trace count — a full serving
        run after warmup performs ZERO further compiles."""
        with self._lock:
            for b in self.buckets:
                if ("prefill", b) not in self._programs:
                    padded = np.zeros((1, b), np.int32)
                    args = (self._pvals_t, self._caches, padded,
                            np.int32(1), np.int32(0))
                    new_caches, _ = self._run(
                        ("prefill", b), "prefill", b,
                        self._prefill_jit, args)
                    self._caches = tuple(new_caches)
            if ("decode",) not in self._programs:
                args = (self._pvals_t, self._caches,
                        np.zeros(self.slots, np.int32),
                        np.zeros(self.slots, np.int32),
                        np.zeros(self.slots, bool))
                new_caches, _ = self._run(
                    ("decode",), "decode", None, self._decode_jit, args)
                self._caches = tuple(new_caches)
            for w in self.verify_widths:
                if ("verify", w) not in self._programs:
                    args = (self._pvals_t, self._caches,
                            np.zeros((self.slots, w), np.int32),
                            np.zeros(self.slots, np.int32),
                            np.ones(self.slots, np.int32),
                            np.zeros(self.slots, bool))
                    new_caches, _ = self._run(
                        ("verify", w), "verify", w, self._verify_jit,
                        args)
                    self._caches = tuple(new_caches)
        return self.retraces

    # -- measured-gate surfaces ----------------------------------------------
    def kv_cache_bytes(self):
        """ACTUAL cache footprint (sum of live buffer nbytes); equals
        ``spec.kv_cache_bytes(slots, kv_dtype)`` — tests pin both
        against the memory_report() row (~0.31× f32 under int8 at the
        default head_dim 16)."""
        return int(sum(int(c.nbytes) for c in self._caches))

    def program_cost(self, kind, bucket=None):
        """XLA cost dict of one acquired program ({} before warmup)."""
        pkey_id = (kind, bucket) if bucket is not None else (kind,)
        return dict(self._program_costs.get(pkey_id) or {})

    def program_memory(self, kind, bucket=None):
        pkey_id = (kind, bucket) if bucket is not None else (kind,)
        return dict(self._program_memory.get(pkey_id) or {})

    def decode_bytes_per_token(self):
        """XLA cost-analysis bytes of ONE decode step divided by the
        lanes it advances — the per-token cost of cached decode."""
        cost = self.program_cost("decode")
        b = float(cost.get("bytes accessed", 0.0))
        return b / self.slots if b else None

    def reprefill_bytes_per_token(self, bucket=None):
        """Bytes of the CACHELESS re-prefill baseline at a seq bucket:
        what one generated token costs a server that recomputes the
        whole prompt instead of reading the cache. Compiled lazily (it
        is a measurement baseline, not a serving program — excluded
        from warmup and from the zero-fresh-compiles pin)."""
        b = self.buckets[-1] if bucket is None else bucket
        pkey_id = ("reprefill", b)
        with self._lock:
            if pkey_id not in self._programs:
                args = (self._pvals_t, np.zeros((1, b), np.int32),
                        np.int32(b))
                self._run(pkey_id, "reprefill", b,
                          self._reprefill_jit, args)
        cost = self.program_cost("reprefill", b)
        v = float(cost.get("bytes accessed", 0.0))
        return v or None

    # -- observability --------------------------------------------------------
    def report(self, reset=False):
        with self._lock:
            out = {
                "id": self.telemetry_id,
                "slots": self.slots,
                "seq_buckets": list(self.buckets),
                "max_seq": self.spec.max_seq,
                "free_slots": len(self._free),
                "retraces": self._materialized,
                "compile_cache_loads": self._cache_loads,
                "prefills": self._prefills,
                "decode_steps": self._decode_steps,
                "verify_steps": self._verify_steps,
                "tokens": self._tokens,
                "kv_dtype": self.kv_dtype,
                "kv_cache_bytes": self.kv_cache_bytes(),
                "kv_cache_accounted_bytes":
                    self.spec.kv_cache_bytes(self.slots,
                                             kv_dtype=self.kv_dtype),
                "kv_cache_f32_bytes":
                    self.spec.kv_cache_bytes(self.slots),
                "decode_bytes_per_token": self.decode_bytes_per_token(),
                "donate": self._donate,
            }
            if reset:
                self._prefills = 0
                self._decode_steps = 0
                self._verify_steps = 0
                self._tokens = 0
        return out
