"""The LVLM serving engine: composes the survey's taxonomy end-to-end.

One ``Engine`` drives a REAL jitted model (fixed-shape slot pool, the XLA
analogue of vLLM's preallocated physical blocks) under any scheduler from
scheduler.py, with the taxonomy dimensions as config switches:

  dim 1  visual token compression  -- pluggable ``CompressionStrategy``
         objects applied to each request's visual embeddings before
         prefill. Like decoders, compression is PER-REQUEST: the engine
         keeps a compressor registry (``Engine(compressors=...)``), each
         request may name its own strategy (``Request.compression``), and
         KV accounting / admission / prefix-cache keys all use the
         POST-compression token counts of the resolved strategy.
  dim 2a KV selection              -- post-prefill cache compaction with
         position-exact masking (slot_pos caches); attention-free selectors
         (l2 / streaming) run live in the engine; attention-score selectors
         (snapkv/h2o) are library-level (they need the attention matrices
         the scanned production path deliberately never materializes --
         the survey's §V "alternative proxy for token salience" point).
  dim 2b prefix caching            -- RadixAttention-style longest-prefix
         reuse backed by host snapshots of the dense slot cache.
  dim 2c scheduling                -- static | continuous | mlfq | chunked
         (chunked prefill runs real ``model.extend`` chunk continuation).
  dim 4  decoding                  -- pluggable ``Decoder`` strategies: the
         per-iteration token emission is a hook (``decoder.engine_decode``)
         so greedy/sampling/speculative/early-exit all run behind one
         interface (adapters in ``repro.api.decoders``; the standalone
         drivers in core/decoding remain the library layer). Every request
         may carry its OWN strategy (``Request.decoder``): the engine keeps
         a decoder registry, groups the decode-phase slots by strategy each
         iteration, and charges each group its true virtual-clock cost --
         speculative runs all its slots per jitted draft/verify call
         (draft caches live in a second slot pool), early-exit slices each
         slot to a batch-1 cache for its host-side layer loop.

NOTE: ``repro.api`` (``LVLM`` / ``GenerationConfig``) is the public surface;
construct ``Engine`` directly only for internal-layer control.

Time is a virtual clock advanced by an analytic per-iteration cost model
(``CostModel``), so TTFT/TPOT/JCT metrics are deterministic and
hardware-independent -- they are model outputs, not measurements, on a CPU
and on a TPU alike. The jitted programs themselves run on whatever backend
JAX selects (``chip_smoke.py`` drives them on one TPU v5e).
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CompressionConfig
from repro.core.decoding.sampling import sample_token
from repro.core.kv_cache.selection import SELECTORS
from repro.core.serving.disaggregation import CostModel
from repro.core.serving.request import Request, State, summarize
from repro.core.serving.scheduler import SCHEDULERS
from repro.core.token_compression.policy import (CompressionStrategy,
                                                 LIVE_KV_SELECTORS)


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8
    cache_len: int = 256
    scheduler: str = "continuous"
    # KV token capacity the continuous/mlfq schedulers (and the serving
    # layer's admission watermarks) budget against; None = the dense slot
    # pool's size, max_batch * cache_len. Setting it LOWER creates KV
    # pressure before the slot pool binds -- the admission-deferral tests
    # and the async server's watermarks use exactly that.
    kv_capacity_tokens: Optional[int] = None
    chunk_size: int = 32                 # chunked-prefill chunk
    token_budget: int = 128              # chunked-prefill per-iter budget
    temperature: float = 0.0
    top_k: int = 0                       # 0 = no top-k warp
    top_p: float = 0.0                   # 0 = no nucleus warp
    eos_id: int = -1                     # -1 = never stop on eos
    seed: int = 0
    decoder: str = "sampling"            # sampling|greedy|speculative|early_exit
    #   DEFAULT strategy; any request may override it per-request via
    #   ``Request.decoder`` (speculative/early_exit resolve via
    #   repro.api.decoders; an explicit Decoder instance passed to
    #   Engine(..., decoder=) takes precedence for the default, and
    #   Engine(..., decoders={name: inst}) registers named strategies)
    compression: CompressionConfig = dataclasses.field(
        default_factory=CompressionConfig)
    #   DEFAULT compression config for the internal layer; the facade now
    #   passes a CompressionStrategy object instead (Engine(compressor=))
    #   and leaves this at its default. Any request may override the
    #   strategy per-request via ``Request.compression``.
    prefix_cache: bool = False
    prefix_block: int = 16               # reuse granularity (tokens)
    prefix_cap: int = 64                 # max cached prefixes (LRU-evicted)
    cost: CostModel = dataclasses.field(default_factory=CostModel)
    # runtime sanitizer (repro.analysis.sanitizer): conservation asserts
    # at step/abort boundaries -- slot table, draft-pool rows, prefix
    # pins, kv accounting. None = follow the REPRO_SANITIZE env var
    # (CI's smoke job sets it); True/False force it per engine.
    sanitize: Optional[bool] = None


class SamplingEngineDecoder:
    """Default decoder hook: one fixed-shape jitted decode step over the
    whole slot pool, then temperature/top-k/top-p sampling (dim 4 baseline).

    The hook contract (duck-typed; richer adapters live in
    ``repro.api.decoders``):

      engine_decode(engine, reqs) -> {slot: [emitted tokens]}

    The decoder owns the forward pass AND the slot bookkeeping
    (``pool`` / ``slot_pos`` / ``slot_last_tok``); the engine handles
    request bookkeeping (generated, eos, DONE) from the emitted map.
    An optional ``validate(engine)`` runs once at Engine construction.
    """
    name = "sampling"

    def __init__(self, greedy: bool = False):
        self.greedy = greedy
        # instance name follows the mode so the engine's decoder registry
        # never routes "sampling" requests to a greedy instance (or splits
        # one strategy into two groups); subclasses' class attrs agree
        self.name = "greedy" if greedy else "sampling"

    def stats(self) -> Dict:
        return {}

    def engine_decode(self, eng: "Engine", reqs: List[Request]) -> Dict:
        ec = eng.ec
        prof = eng.profiler
        if prof.enabled:
            prof.site_begin("decode_inputs")
        toks = np.zeros((ec.max_batch, 1), np.int32)
        # fixed-shape decode runs EVERY slot; inactive slots (empty or
        # mid-prefill) must not corrupt real cache entries, so their write
        # lands on the reserved scratch position cache_len-1 (requests are
        # capacity-checked to never reach it).
        pos = np.full(ec.max_batch, ec.cache_len - 1, np.int32)
        for r in reqs:
            toks[r._slot, 0] = eng.slot_last_tok[r._slot]
            pos[r._slot] = eng.slot_pos[r._slot]
        toks, pos = jnp.asarray(toks), jnp.asarray(pos)
        if prof.enabled:
            prof.site_end("decode_inputs")
        logits, eng.pool = eng._jit_decode(eng.params, eng.pool, toks, pos)
        eng.key, k1 = jax.random.split(eng.key)
        temp = 0.0 if self.greedy else ec.temperature
        sampled = sample_token(k1, logits, temperature=temp, top_k=ec.top_k,
                               top_p=ec.top_p)
        if prof.enabled:
            prof.wait_begin("wait:decode")
        nxt = np.asarray(sampled)
        if prof.enabled:
            prof.wait_end("wait:decode")
        emitted: Dict[int, List[int]] = {}
        for r in reqs:
            s = r._slot
            tok = int(nxt[s])
            eng.slot_last_tok[s] = tok
            eng.slot_pos[s] += 1
            emitted[s] = [tok]
        return emitted


def _make_default_decoder(name: str):
    if name in ("sampling", "greedy"):
        return SamplingEngineDecoder(greedy=(name == "greedy"))
    # strategy adapters live one layer up; resolve lazily to keep
    # repro.core importable without repro.api
    from repro.api.decoders import make_decoder
    return make_decoder(name)


def _make_compressor(name: str):
    # preset/parametric names ("fastv-0.5", "streaming-kv-64") resolve
    # one layer up; lazy for the same importability reason as decoders
    from repro.api.compressors import make_compressor
    return make_compressor(name)


def _slot_get(pool, slot):
    """Slice one slot's cache out of the pool as a batch-1 cache."""
    return jax.tree.map(lambda a: a[:, slot:slot + 1], pool)


def _slot_set(pool, slot, one):
    return jax.tree.map(lambda a, s: a.at[:, slot].set(s[:, 0]), pool, one)


class Engine:
    def __init__(self, model, params, ec: EngineConfig, *, decoder=None,
                 decoders: Optional[Dict] = None, compressor=None,
                 compressors: Optional[Dict] = None, tracer=None,
                 profiler=None):
        cfg = model.cfg
        self.ec = ec
        self.params = params
        # default compression strategy: an explicit strategy object wins;
        # otherwise wrap EngineConfig.compression (internal-layer path)
        self.compressor = compressor if compressor is not None \
            else CompressionStrategy(ec.compression)
        cc0 = getattr(self.compressor, "cc", ec.compression)
        compacting = (cc0.kv_selector in LIVE_KV_SELECTORS
                      and cc0.kv_budget > 0)
        if compacting and cfg.family not in ("dense", "vlm", "moe"):
            raise ValueError("KV compaction needs an attention-cache family")
        if compacting and cfg.use_mla:
            raise ValueError("engine KV compaction on the MLA latent cache "
                             "is not implemented (it is itself compressed)")
        if compacting and ec.prefix_cache:
            raise ValueError("prefix reuse + live compaction not composable "
                             "(compacted caches are request-specific)")
        self.compacting = compacting
        if compacting:
            # position-exact caches: full-length slot_pos ring (window off)
            cfg = cfg.with_(sliding_window=ec.cache_len)
            from repro.models.registry import build
            model = build(cfg)
        self.model = model
        self.cfg = cfg
        self.windowed = compacting

        self.pool = model.init_cache(ec.max_batch, ec.cache_len,
                                     windowed=self.windowed)
        self.slot_req: List[Optional[Request]] = [None] * ec.max_batch
        self.slot_pos = np.zeros(ec.max_batch, np.int64)   # next write pos
        self.slot_last_tok = np.zeros(ec.max_batch, np.int64)
        self.slot_nv = np.zeros(ec.max_batch, np.int64)    # visual offset

        kw: Dict = {}
        if ec.scheduler in ("continuous", "mlfq"):
            kw = dict(max_batch=ec.max_batch,
                      kv_capacity_tokens=self.kv_capacity_tokens)
        elif ec.scheduler == "chunked":
            kw = dict(max_batch=ec.max_batch, token_budget=ec.token_budget,
                      chunk_size=ec.chunk_size)
        elif ec.scheduler == "static":
            kw = dict(batch_size=ec.max_batch)
        self.sched = SCHEDULERS[ec.scheduler](**kw)

        self.waiting: List[Request] = []
        self.running: List[Request] = []
        self.finished: List[Request] = []
        self.aborted: List[Request] = []
        self.clock = 0.0
        self.key = jax.random.PRNGKey(ec.seed)
        self.iters = 0
        # cumulative decode-phase virtual-clock cost per strategy group
        # (prefill cost is request-, not strategy-, attributed)
        self.group_costs: Dict[str, float] = {}
        # prefix cache: host map keyed by (compression variant, tokens) --
        # a prefill is only reusable under the SAME variant -- longest
        # block-aligned prefix match, true-LRU eviction (lookup hits
        # move-to-end; see _prefix_lookup)
        self._prefix: "OrderedDict[Tuple, Tuple]" = OrderedDict()
        # in-flight pin counts, keyed like _prefix by (variant, tokens):
        # entries a live request hit stay resident (LRU eviction skips
        # them); released at retire/abort
        self._prefix_pins: Dict[Tuple[str, Tuple[int, ...]], int] = {}
        self.prefix_hit_tokens = 0
        self.prefix_total_tokens = 0
        # cluster-shared prefix tier (duck-typed: lookup/insert; installed
        # by repro.cluster so a prefix cached on ANY replica short-circuits
        # prefill here). Remote hits are installed locally and pay one
        # modeled KV-link transfer on this engine's clock.
        self.prefix_share = None
        self.remote_prefix_hits = 0
        self._iter_transfer_cost = 0.0
        # live KV migration (disaggregated serving): rid -> export ticket.
        # The ticket owns the source slot and any prefix pin from
        # ``export_kv`` until ``complete_export`` (source release) or
        # ``cancel_export`` (ownership back to the request).
        self._exports: Dict[int, Dict] = {}
        self.migrated_in = 0
        self.migrated_out = 0

        # named functions, so that each XLA program carries the name of
        # the engine call it serves (``jit_engine_prefill``, ...)
        def engine_prefill(p, batch):
            return self.model.prefill(p, batch, cache_len=ec.cache_len,
                                      windowed=self.windowed)

        def engine_extend(p, cache, tokens, start):
            return self.model.extend(p, cache, tokens, start)

        def engine_decode(p, pool, toks, pos):
            return self.model.decode_step(p, pool, toks, pos,
                                          windowed=self.windowed)

        self._jit_prefill = jax.jit(engine_prefill)
        self._jit_extend = jax.jit(engine_extend)
        self._jit_decode = jax.jit(engine_decode)

        # decoder registry: the configured default plus named per-request
        # strategies; unknown names resolve lazily via repro.api.decoders
        # (validated on first use, so registering e.g. early_exit alongside
        # a compacting engine only errors if a request actually asks for it)
        self.decoder = decoder if decoder is not None \
            else _make_default_decoder(ec.decoder)
        self._decoders: Dict[str, object] = {}
        if decoders:
            self._decoders.update(decoders)
        self._default_name = getattr(self.decoder, "name", ec.decoder)
        self._decoders[self._default_name] = self.decoder
        self._validated = set()
        # names marked at submit: only strategies that actually serve a
        # request count toward decoder_stats()'s flat-vs-prefixed choice
        self._used_decoders: set = set()
        self._validate_decoder(self._default_name, self.decoder)

        # compressor registry: the default strategy plus named per-request
        # strategies; unknown names resolve lazily via repro.api (preset /
        # parametric grammar), validated on first use like decoders
        self._compressors: Dict[str, object] = {}
        if compressors:
            self._compressors.update(compressors)
        self._default_comp_name = getattr(self.compressor, "name", "none")
        self._compressors[self._default_comp_name] = self.compressor
        self._validated_comps: set = set()
        # per-strategy visual-token counters: name -> [in, out] (the
        # prefill-token-reduction signal compression_stats() reports)
        self._comp_counts: Dict[str, List[int]] = {}
        self._validate_compressor(self._default_comp_name, self.compressor)

        # observability: the tracer every instrumentation site guards on
        # (``if self.tracer.enabled:`` -- NULL_TRACER keeps the disabled
        # hot path call-free). ``trace_replica`` is this engine's track in
        # a fleet-shared trace; the Router assigns real indices.
        if tracer is None:
            from repro.obs.trace import NULL_TRACER
            tracer = NULL_TRACER
        self.tracer = tracer
        self.trace_replica = 0

        # continuous profiling: same zero-overhead-when-off discipline as
        # the tracer -- every hot-path site guards on ``profiler.enabled``
        # and sites only read clocks, so profiled runs stay bit-identical
        if profiler is None:
            from repro.obs.profile import NULL_PROFILER
            profiler = NULL_PROFILER
        self.profiler = profiler

        # runtime sanitizer: resolved once (config wins over env)
        if ec.sanitize is not None:
            self.sanitize = bool(ec.sanitize)
        else:
            from repro.analysis.sanitizer import sanitize_enabled
            self.sanitize = sanitize_enabled()

    def _sanitize_check(self, where: str) -> None:
        """Raise ``SanitizerError`` if a conservation invariant is
        violated (slot/draft-row/pin/kv accounting; see
        repro.analysis.sanitizer). Called at step and abort boundaries
        when ``sanitize`` is on."""
        from repro.analysis.sanitizer import (assert_conserved,
                                              check_engine_conservation)
        assert_conserved(self, check_engine_conservation, where)

    # ----------------------------------------------------------- decoders --
    def _validate_decoder(self, name: str, dec) -> None:
        if name in self._validated:
            return
        validate = getattr(dec, "validate", None)
        if validate is not None:
            validate(self)
        self._validated.add(name)

    def _resolve_decoder(self, name: Optional[str]) -> Tuple[str, object]:
        """Per-request strategy resolution: None -> the engine default."""
        if name is None:
            return self._default_name, self.decoder
        dec = self._decoders.get(name)
        if dec is None:
            dec = _make_default_decoder(name)
            self._decoders[name] = dec
        self._validate_decoder(name, dec)
        return name, dec

    def decoder_stats(self) -> Dict:
        """Counters of every strategy that served a request. A single
        strategy reports flat keys (back-compat); a mixed run prefixes
        them with the strategy name."""
        names = [n for n in self._decoders if n in self._used_decoders]
        if not names:                     # nothing submitted yet
            names = [self._default_name]
        if len(names) == 1:
            return dict(self._decoders[names[0]].stats())
        out: Dict = {}
        for n in names:
            for k, v in self._decoders[n].stats().items():
                out[f"{n}/{k}"] = v
        return out

    # -------------------------------------------------------- compressors --
    def _validate_compressor(self, name: str, comp) -> None:
        if name in self._validated_comps:
            return
        validate = getattr(comp, "validate", None)
        if validate is not None:
            validate(self)
        self._validated_comps.add(name)

    def _resolve_compressor(self, name: Optional[str]) -> Tuple[str, object]:
        """Per-request compression resolution: None -> the engine default;
        otherwise a registered strategy or any preset/parametric name
        (resolved lazily, mirror of ``_resolve_decoder``)."""
        if name is None:
            return self._default_comp_name, self.compressor
        comp = self._compressors.get(name)
        if comp is None:
            comp = _make_compressor(name)
            self._compressors[name] = comp
        self._validate_compressor(name, comp)
        return name, comp

    def _stamp_compressed_nv(self, req: Request) -> None:
        """Resolve the request's strategy and stamp its POST-compression
        visual count (idempotent; the basis of all KV accounting)."""
        if req.nv_compressed is not None or req.visual_embeds is None:
            return
        _, comp = self._resolve_compressor(req.compression)
        req.nv_compressed = int(
            comp.compressed_token_count(len(req.visual_embeds)))

    def compression_stats(self) -> Dict[str, Dict]:
        """Per-strategy visual-token reduction of every strategy that
        compressed a request's prefill: ``{name: {visual_tokens_in,
        visual_tokens_out, prefill_token_reduction}}``."""
        out: Dict[str, Dict] = {}
        for name, (vin, vout) in self._comp_counts.items():
            out[name] = {
                "visual_tokens_in": vin,
                "visual_tokens_out": vout,
                "prefill_token_reduction":
                    (1.0 - vout / vin) if vin else 0.0,
            }
        return out

    # ------------------------------------------------------------- intake --
    def submit(self, req: Request) -> None:
        name, dec = self._resolve_decoder(req.decoder)
        self._used_decoders.add(name)
        cname, _comp = self._resolve_compressor(req.compression)
        req._comp_name = cname
        self._stamp_compressed_nv(req)
        # speculative slots verify up to gamma positions past the committed
        # stream: reserve that slack so block writes stay clear of the
        # scratch position (and schedulers account it as KV footprint)
        req.lookahead = max(req.lookahead,
                            int(getattr(dec, "lookahead_tokens", 0)))
        # capacity is checked against what actually lands in the cache:
        # the POST-compression prompt length
        need = req.kv_prompt_len + req.max_new_tokens + req.lookahead
        if need > self.ec.cache_len - 1:
            raise ValueError(
                f"request {req.rid} needs {need} tokens"
                f" (incl. {req.lookahead} decode lookahead);"
                f" cache_len-1 = {self.ec.cache_len - 1} available"
                " (last position is the inactive-slot scratch)")
        req.arrival = max(req.arrival, self.clock)
        self.waiting.append(req)
        if self.profiler.enabled:
            # a request the async server admitted is already waiting
            # since its admission began: that interval keeps its start
            self.profiler.interval_begin("queue_wait", req.rid, rid=req.rid)
        if self.tracer.enabled:
            self.tracer.span_begin(
                "request", req.rid, replica=self.trace_replica,
                vt=self.clock, prompt_len=req.prompt_len,
                decoder=name, compression=cname)

    # -------------------------------------------------- kv accounting --
    @property
    def kv_capacity_tokens(self) -> int:
        """Token capacity admission budgets against (dense pool size unless
        EngineConfig.kv_capacity_tokens narrows it)."""
        if self.ec.kv_capacity_tokens is not None:
            return self.ec.kv_capacity_tokens
        return self.ec.max_batch * self.ec.cache_len

    def _kv_block(self) -> int:
        return int(getattr(self.sched, "block_size", 16))

    def kv_request_tokens(self, req: Request) -> int:
        """Block-rounded KV reservation one request commits the pool to:
        POST-compression prompt + max_new + decode lookahead (speculative
        gamma AND the compression strategy resolve via the request even
        before submit, so admission watermarks and ``least_kv`` routing
        never over-reserve for tokens the pruner will drop)."""
        la = req.lookahead
        if req.decoder is not None or la == 0:
            _, dec = self._resolve_decoder(req.decoder)
            la = max(la, int(getattr(dec, "lookahead_tokens", 0)))
        self._stamp_compressed_nv(req)
        bs = self._kv_block()
        if req.handoff and not getattr(req, "_imported", False):
            # prefill-role accounting: a handoff request decodes on the
            # importing engine -- this pool only ever holds its prompt KV
            # plus the first token, so reserving max_new here would let
            # one video burst starve the prefill replica's admission
            need = req.kv_prompt_len + 1
        else:
            need = req.kv_prompt_len + req.max_new_tokens + la
        return ((need + bs - 1) // bs) * bs

    def kv_committed_tokens(self, include_waiting: bool = True) -> int:
        """Total KV reservation of live requests (the admission-control
        pressure signal; returns to baseline after finish/abort)."""
        live = [r for r in self.running if r.state != State.DONE]
        if include_waiting:
            live += [r for r in self.waiting if r.state != State.DONE]
        return sum(self.kv_request_tokens(r) for r in live)

    # -------------------------------------------------------- lifecycle --
    def _release_request(self, r: Request) -> None:
        """Free every resource a request holds: its slot in the main pool,
        any strategy-held per-slot state (speculative draft-pool row), and
        its prefix-cache pin. The gamma lookahead reservation is freed
        implicitly: capacity accounting only counts live requests."""
        slot = getattr(r, "_slot", None)
        if slot is not None and self.slot_req[slot] is r:
            self.slot_req[slot] = None
            for dec in self._decoders.values():
                release = getattr(dec, "release_slot", None)
                if release is not None:
                    release(slot)
        key = getattr(r, "_prefix_pin", None)
        if key is not None:
            n = self._prefix_pins.get(key, 0) - 1
            if n > 0:
                self._prefix_pins[key] = n
            else:
                self._prefix_pins.pop(key, None)
            r._prefix_pin = None

    def abort(self, rid: int) -> bool:
        """Cancel a request mid-flight (the serving layer's cancellation
        path). Frees the main KV slot, the speculative draft-pool slot,
        the reserved lookahead, and any prefix-cache pin; the request is
        marked ``aborted`` and never reaches ``finished``. Returns False
        if ``rid`` is unknown or already retired."""
        for pool in (self.waiting, self.running):
            for r in pool:
                if r.rid == rid and r.state != State.DONE:
                    pool.remove(r)
                    self._release_request(r)
                    r.state = State.DONE
                    r.aborted = True
                    if self.profiler.enabled:
                        self.profiler.interval_drop("queue_wait", rid)
                    self.aborted.append(r)
                    if self.tracer.enabled:
                        # closes the request span AND any open stage span
                        # (prefill, kv_migration) so an abort never
                        # orphans part of the trace
                        self.tracer.span_abort(rid,
                                               replica=self.trace_replica,
                                               vt=self.clock)
                    if self.sanitize:
                        self._sanitize_check(f"Engine.abort(rid={rid})")
                    return True
        return False

    # ---------------------------------------------------------- migration --
    # Live KV migration protocol (disaggregated prefill/decode, drain):
    #   export_kv (source pin) -> import_kv (target commit) ->
    #   complete_export (source release), or cancel_export to back out.
    # The exporting request stays in ``running`` in State.MIGRATING and
    # keeps its slot until the source release, so a target-side failure
    # before commit loses nothing (exactly-once: the request either
    # resumes here via cancel_export or decodes exactly once over there).

    def can_export(self, req: Request) -> bool:
        """True when this engine can hand the request's KV to a sibling:
        compacted caches are request-specific (position-masked rings) and
        decoders with per-slot state (speculative draft-pool rows) cannot
        be rebuilt from a bare KV snapshot on the importing side."""
        if self.compacting:
            return False
        _, dec = self._resolve_decoder(req.decoder)
        return getattr(dec, "release_slot", None) is None

    def export_kv(self, rid: int) -> Dict:
        """Pin a live request for migration and snapshot its KV.

        Returns the export ticket: the host-side snapshot of the slot's
        cache up to the current position plus the per-slot cursors and the
        source clock (the transfer-time anchor). The ticket owns the
        source slot and any prefix pin until ``complete_export`` /
        ``cancel_export``; the request stops decoding here (MIGRATING)."""
        req = next((r for r in self.running
                    if r.rid == rid
                    and r.state in (State.DECODE, State.MIGRATING)), None)
        if req is None:
            raise KeyError(f"export_kv: rid {rid} is not migratable here")
        if rid in self._exports:
            raise RuntimeError(f"export_kv: rid {rid} already has an "
                               "export pin")
        if not self.can_export(req):
            raise RuntimeError(
                f"export_kv: rid {rid} is not exportable (compacted cache "
                "or per-slot decoder state)")
        slot = req._slot
        pos = int(self.slot_pos[slot])
        if self.profiler.enabled:
            self.profiler.site_begin("kv_export")
        snap = jax.tree.map(lambda a: a[:, :, :pos],
                            _slot_get(self.pool, slot))
        if self.profiler.enabled:
            self.profiler.site_end("kv_export")
        ticket = {
            "rid": rid, "req": req, "snap": snap, "pos": pos,
            "last_tok": int(self.slot_last_tok[slot]),
            "nv": int(self.slot_nv[slot]),
            "slot": slot, "clock": self.clock,
            "prefix_pin": getattr(req, "_prefix_pin", None),
        }
        # pin ownership moves to the ticket: the target never inherits the
        # source's prefix pin, and the source release must still find it
        # after the target overwrites the request's slot binding
        req._prefix_pin = None
        req._export_pin = rid
        req.state = State.MIGRATING
        self._exports[rid] = ticket
        if self.tracer.enabled:
            self.tracer.span_begin(
                "kv_migration", rid, replica=self.trace_replica,
                vt=self.clock, kv_tokens=pos)
            self.tracer.counter(
                "migration_bytes_inflight", self._export_bytes_inflight(),
                replica=self.trace_replica, vt=self.clock)
        return ticket

    def _export_bytes_inflight(self) -> int:
        """Modeled bytes of every KV snapshot currently pinned for
        migration out of this engine (a trace counter track)."""
        bpt = int(getattr(self.ec.cost, "kv_bytes_per_token", 0))
        return sum(int(t["pos"]) for t in self._exports.values()) * bpt

    def complete_export(self, rid: int) -> None:
        """Source-side release of a migrated request: the importing engine
        has committed, so free everything the export ticket owns -- the
        slot (and any decoder per-slot row), the prefix pin, and the
        running-list entry. Never touches ``req.state``: the importing
        engine owns the request now."""
        ticket = self._exports.pop(rid)
        req = ticket["req"]
        self.running.remove(req)
        slot = ticket["slot"]
        if self.slot_req[slot] is req:
            self.slot_req[ticket["slot"]] = None
            for dec in self._decoders.values():
                release = getattr(dec, "release_slot", None)
                if release is not None:
                    release(slot)
        key = ticket["prefix_pin"]
        if key is not None:
            n = self._prefix_pins.get(key, 0) - 1
            if n > 0:
                self._prefix_pins[key] = n
            else:
                self._prefix_pins.pop(key, None)
        req._export_pin = None
        self.migrated_out += 1
        if self.tracer.enabled:
            self.tracer.instant("kv_export_complete", rid,
                                replica=self.trace_replica, vt=self.clock)
            self.tracer.counter(
                "migration_bytes_inflight", self._export_bytes_inflight(),
                replica=self.trace_replica, vt=self.clock)
        if self.sanitize:
            self._sanitize_check(f"Engine.complete_export(rid={rid})")

    def cancel_export(self, rid: int) -> None:
        """Back out an export (no sibling could import): the request
        resumes decoding HERE -- pin ownership returns to it, and its
        handoff flag clears so KV accounting covers the in-place decode."""
        ticket = self._exports.pop(rid, None)
        if ticket is None:
            return
        req = ticket["req"]
        req._prefix_pin = ticket["prefix_pin"]
        req._export_pin = None
        req.handoff = False
        req.state = State.DECODE
        if self.tracer.enabled:
            self.tracer.span_end("kv_migration", rid,
                                 replica=self.trace_replica,
                                 vt=self.clock, cancelled=True)
            self.tracer.counter(
                "migration_bytes_inflight", self._export_bytes_inflight(),
                replica=self.trace_replica, vt=self.clock)
        if self.sanitize:
            self._sanitize_check(f"Engine.cancel_export(rid={rid})")

    def import_kv(self, req: Request, ticket: Dict, *,
                  ready_at: float = 0.0) -> None:
        """Import-commit side of a migration: bind a free slot, restore
        the exported KV snapshot and per-slot cursors, and resume the
        request in DECODE. Its first decode step here is gated on
        ``ready_at`` (source export clock + modeled KV-link transfer), so
        the transfer cost lands on this engine's virtual clock before the
        request's next token. Raises when no slot is free or the snapshot
        cannot fit -- the caller still holds the source pin and may try a
        sibling or cancel."""
        if self.compacting:
            raise RuntimeError("import_kv: compacting engines cannot host "
                               "migrated KV (position-masked caches)")
        if any(r.rid == req.rid for r in self.running + self.waiting):
            raise ValueError(f"import_kv: rid {req.rid} already live here")
        name, _dec = self._resolve_decoder(req.decoder)
        self._used_decoders.add(name)
        cname, _comp = self._resolve_compressor(req.compression)
        req._comp_name = cname
        pos = int(ticket["pos"])
        remaining = req.max_new_tokens - len(req.generated)
        if pos + remaining > self.ec.cache_len - 1:
            raise ValueError(
                f"import_kv: rid {req.rid} needs {pos + remaining} tokens; "
                f"cache_len-1 = {self.ec.cache_len - 1} available")
        slot = self._free_slot()
        req._slot = slot
        self.slot_req[slot] = req
        if self.profiler.enabled:
            self.profiler.site_begin("kv_transfer")
        self._install_snap(slot, ticket["snap"])
        if self.profiler.enabled:
            self.profiler.site_end("kv_transfer")
        self.slot_pos[slot] = pos
        self.slot_last_tok[slot] = ticket["last_tok"]
        self.slot_nv[slot] = ticket["nv"]
        req._imported = True
        req._ready_at = max(self.clock, ready_at)
        req.state = State.DECODE
        req.prefill_done = len(req.tokens)
        self.migrated_in += 1
        self.running.append(req)
        if self.tracer.enabled:
            # the import commit closes the migration span ON THE TARGET
            # replica and hands the request's trace track over with it
            # (Tracer ownership follows the kv_migration end). ``vt`` is
            # the transfer-complete time -- >= the source's export clock,
            # so the request's virtual timeline never rewinds across the
            # replica boundary.
            self.tracer.span_end(
                "kv_migration", req.rid, replica=self.trace_replica,
                vt=req._ready_at, kv_tokens=pos)
        if self.sanitize:
            self._sanitize_check(f"Engine.import_kv(rid={req.rid})")

    # ------------------------------------------------------------- prefix --
    def _prefix_variant(self, name: Optional[str]) -> str:
        """Compression-variant component of every prefix-cache key: the
        request's strategy name (None -> the engine default). A cached
        prefill is only reusable under the SAME compression variant -- a
        ``fastv-0.5`` prefill must never serve a ``none`` lookup."""
        return name if name is not None else self._default_comp_name

    def _prefix_lookup(self, tokens: List[int], touch: bool = True,
                       variant: Optional[str] = None
                       ) -> Tuple[int, Optional[Tuple]]:
        """Longest block-aligned cached prefix of ``tokens`` under the
        given compression ``variant``.

        Inserted keys are always multiples of ``prefix_block``, so probing
        descending block-aligned lengths is exact and O(len/block) probes
        per prefill instead of the old O(#entries x prefix_len) scan. A hit
        is an LRU touch (move-to-end) unless ``touch=False`` -- the pure
        probe routing layers use (cluster prefix-affinity), where only a
        real prefill hit should refresh recency."""
        bs = self.ec.prefix_block
        v = self._prefix_variant(variant)
        t = tuple(tokens)
        best_k, best = 0, None
        for k in range((len(t) // bs) * bs, 0, -bs):
            hit = self._prefix.get((v, t[:k]))
            if hit is not None:
                best_k, best = k, hit
                break
        if self.prefix_share is not None:
            if self.profiler.enabled:
                self.profiler.site_begin("prefix_tier_probe")
            rk, rsnap = self.prefix_share.lookup(v, t, block=bs, touch=touch)
            if self.profiler.enabled:
                self.profiler.site_end("prefix_tier_probe")
            if rk > best_k:
                # remote hit beats the local one: install it locally (one
                # modeled KV-link transfer, charged to this step's clock)
                # so later lookups here are local
                if touch:
                    if self.profiler.enabled:
                        self.profiler.site_begin("prefix_tier_install")
                    self._prefix_store((v, t[:rk]), rsnap, rk)
                    self._iter_transfer_cost += self.ec.cost.transfer_time(rk)
                    self.remote_prefix_hits += 1
                    if self.profiler.enabled:
                        self.profiler.site_end("prefix_tier_install")
                return rk, (rsnap, rk)
        if best is not None:
            if touch:
                self._prefix.move_to_end((v, t[:best_k]))
            return best_k, best
        return 0, None

    def _prefix_insert(self, tokens: List[int], slot: int, length: int,
                       variant: Optional[str] = None):
        bs = self.ec.prefix_block
        k = (min(length, len(tokens)) // bs) * bs
        if k == 0:
            return
        key = (self._prefix_variant(variant), tuple(tokens[:k]))
        if key in self._prefix:
            self._prefix.move_to_end(key)            # re-insert = LRU touch
            return
        snap = jax.tree.map(lambda a: a[:, :, :k], _slot_get(self.pool, slot))
        self._prefix_store(key, snap, k)
        if self.prefix_share is not None:
            # publish to the cluster-shared tier: a sibling replica's next
            # prefill of this prefix short-circuits via the tier
            if self.profiler.enabled:
                self.profiler.site_begin("prefix_tier_install")
            self.prefix_share.insert(key[0], key[1], snap, k)
            if self.profiler.enabled:
                self.profiler.site_end("prefix_tier_install")

    def _prefix_store(self, key: Tuple, snap, k: int) -> None:
        """Insert an entry into the LOCAL prefix cache with LRU eviction
        (shared by local inserts and shared-tier hit installs)."""
        if key in self._prefix:
            self._prefix.move_to_end(key)
            return
        self._prefix[key] = (snap, k)
        while len(self._prefix) > self.ec.prefix_cap:
            # least-recent UNPINNED entry; pinned ones (a live request hit
            # them) stay resident until their requests retire/abort
            victim = next((c for c in self._prefix
                           if not self._prefix_pins.get(c)), None)
            if victim is None:
                break
            del self._prefix[victim]

    def _install_snap(self, slot: int, snap) -> None:
        def put(a, s):
            return a.at[:, slot].set(
                jax.lax.dynamic_update_slice_in_dim(a[:, slot], s[:, 0], 0,
                                                    axis=1))
        self.pool = jax.tree.map(put, self.pool, snap)

    # ------------------------------------------------------------ prefill --
    def _free_slot(self) -> int:
        for i, r in enumerate(self.slot_req):
            if r is None:
                return i
        raise RuntimeError("no free slot (scheduler overcommitted)")

    def _prompt_query_embeds(self, req: Request):
        """Text-prompt embeddings [1, Q, d] for cross-modal pruners
        (sparsevlm / cdpruner rank visual tokens by instruction
        relevance). The prompt IS known at prefill time, so the engine
        threads it instead of the old silent ``query=None`` degradation
        to query-free behavior."""
        if not req.tokens or not isinstance(self.params, dict) \
                or "embed" not in self.params:
            return None
        from repro.models.layers import embed_tokens
        return embed_tokens(self.params["embed"],
                            jnp.asarray([req.tokens], jnp.int32))

    def _do_prefill_chunk(self, req: Request, n: int) -> None:
        ec = self.ec
        n = min(n, len(req.tokens) - req.prefill_done)
        if n <= 0:
            return
        # hot-path site: the whole chunk (compression, prefix probe and
        # forward) -- nested sites (compress, prefix_tier_*) subtract from
        # this site's SELF time, leaving the forward pass itself
        if self.profiler.enabled:
            if req.prefill_done == 0:
                self.profiler.interval_end("queue_wait", req.rid)
            self.profiler.site_begin("prefill_forward", rid=req.rid)
        comp_name = getattr(req, "_comp_name", None) \
            or self._default_comp_name
        if req.prefill_done == 0:
            slot = self._free_slot()
            req._slot = slot
            self.slot_req[slot] = req
            if self.tracer.enabled:
                self.tracer.span_begin("prefill", req.rid,
                                       replica=self.trace_replica,
                                       slot=slot, vt=self.clock)
            # dim 1: the request's compression strategy runs before the
            # visual tokens enter the backbone
            ve = req.visual_embeds
            if ve is not None:
                _, comp = self._resolve_compressor(req.compression)
                nv_in = len(ve)
                if self.tracer.enabled:
                    # vision tokens entering the backbone: the wall-time
                    # delta of this span is the real compression cost the
                    # virtual clock does not model
                    self.tracer.span_begin("compress", req.rid,
                                           replica=self.trace_replica,
                                           vt=self.clock, strategy=comp_name,
                                           nv_in=nv_in)
                if self.profiler.enabled:
                    self.profiler.site_begin("compress")
                if getattr(comp, "encoder_active", True):
                    # the query embed is only built for strategies that
                    # consume it (custom strategies default to yes)
                    q = self._prompt_query_embeds(req) \
                        if getattr(comp, "needs_query", True) else None
                    ve_j, _, _ = comp.compress_prefill(
                        jnp.asarray(ve)[None], query=q)
                    ve_j = ve_j[0]
                    if self.profiler.enabled:
                        self.profiler.wait_begin("wait:compress")
                    ve = np.asarray(ve_j)
                    if self.profiler.enabled:
                        self.profiler.wait_end("wait:compress")
                if self.profiler.enabled:
                    self.profiler.site_end("compress")
                cnt = self._comp_counts.setdefault(comp_name, [0, 0])
                cnt[0] += nv_in
                cnt[1] += len(ve)
                if self.tracer.enabled:
                    self.tracer.span_end("compress", req.rid,
                                         replica=self.trace_replica,
                                         vt=self.clock, nv_out=len(ve))
            req._ve = ve
            self.slot_nv[slot] = 0 if ve is None else len(ve)
            # visual tokens are prefill work too (the dim-1 latency claim)
            self._iter_visual_tokens += int(self.slot_nv[slot])
        slot = req._slot
        nv = int(self.slot_nv[slot])
        start, end = req.prefill_done, req.prefill_done + n

        if req.prefill_done == 0:
            # dim 2b: prefix reuse (text-token prompts), keyed by the
            # request's compression variant
            use, hit = 0, None
            if ec.prefix_cache and req._ve is None:
                hit_k, hit = self._prefix_lookup(req.tokens,
                                                 variant=comp_name)
                self.prefix_total_tokens += len(req.tokens)
                # always recompute >=1 token so we have last-position logits
                use = min(hit_k, len(req.tokens) - 1, end - 1)
            if hit is not None and use > 0:
                key = (comp_name, tuple(req.tokens[:hit_k]))
                self._prefix_pins[key] = self._prefix_pins.get(key, 0) + 1
                req._prefix_pin = key
                snap, _k = hit
                self._install_snap(
                    slot, jax.tree.map(lambda a: a[:, :, :use], snap))
                self.prefix_hit_tokens += use
                one = _slot_get(self.pool, slot)
                sub = jnp.asarray(req.tokens[use:end], jnp.int32)[None]
                logits, one = self._jit_extend(self.params, one, sub,
                                               jnp.int32(use))
                self.pool = _slot_set(self.pool, slot, one)
            else:
                chunk = jnp.asarray(req.tokens[:end], jnp.int32)[None]
                batch = {"tokens": chunk}
                if req._ve is not None:
                    batch["visual_embeds"] = jnp.asarray(req._ve)[None]
                logits, one = self._jit_prefill(self.params, batch)
                self.pool = _slot_set(self.pool, slot, one)
        else:
            chunk = jnp.asarray(req.tokens[start:end], jnp.int32)[None]
            one = _slot_get(self.pool, slot)
            logits, one = self._jit_extend(self.params, one, chunk,
                                           jnp.int32(nv + start))
            self.pool = _slot_set(self.pool, slot, one)

        req.prefill_done = end
        self.slot_pos[slot] = nv + end
        if self.tracer.enabled:
            self.tracer.instant("prefill_chunk", req.rid,
                                replica=self.trace_replica, slot=slot,
                                vt=self.clock, tokens=n)
        if req.prefill_done >= len(req.tokens):
            # prompt complete: first token comes from the last logits
            if ec.prefix_cache and req._ve is None:
                self._prefix_insert(req.tokens, slot, end,
                                    variant=comp_name)
            if self.compacting:
                # dim 2a: KV-side hook of the request's strategy -- on a
                # compacting (windowed) engine each request compacts to
                # its OWN budget; strategies without one skip compaction
                _, comp = self._resolve_compressor(req.compression)
                budget = getattr(comp, "decode_budget", lambda: None)()
                if budget:
                    self._compact_slot(
                        slot, getattr(comp, "kv_selector", "streaming"),
                        budget)
            self.key, k1 = jax.random.split(self.key)
            _, dec = self._resolve_decoder(req.decoder)
            temp = 0.0 if getattr(dec, "greedy", False) else ec.temperature
            first = sample_token(k1, logits[:, -1], temperature=temp,
                                 top_k=ec.top_k, top_p=ec.top_p)[0]
            if self.profiler.enabled:
                self.profiler.wait_begin("wait:prefill")
            tok = int(first)
            if self.profiler.enabled:
                self.profiler.wait_end("wait:prefill")
            req.generated.append(tok)
            req._needs_ttft = True
            self.slot_last_tok[slot] = tok
            if self.tracer.enabled:
                self.tracer.span_end("prefill", req.rid,
                                     replica=self.trace_replica, slot=slot,
                                     vt=self.clock)
            if req.is_finished() or tok == ec.eos_id:
                req.state = State.DONE
            elif req.handoff and self.can_export(req):
                # disaggregated prefill: park for KV export (the serving
                # layer migrates it to a decode replica) instead of
                # entering this engine's decode loop
                req.state = State.MIGRATING
            else:
                req.handoff = False       # not exportable: decode in place
                req.state = State.DECODE
            if req in self.waiting:
                self.waiting.remove(req)
            self.running.append(req)
        if self.profiler.enabled:
            self.profiler.site_end("prefill_forward")

    # ------------------------------------------------------ KV compaction --
    def _compact_slot(self, slot: int, selector: str, budget: int) -> None:
        """dim 2a: evict down to ``budget`` with exact position bookkeeping
        (selector/budget come from the REQUEST's compression strategy).

        Retained entries keep their ORIGINAL positions in ``slot_pos`` (the
        RoPE-consistency requirement the survey's §V flags); evicted slots
        are masked with -1. Dense-slot memory is not reclaimed (that is the
        paged pool's job) -- what the engine proves is output fidelity under
        the eviction policy.
        """
        pos_end = int(self.slot_pos[slot])
        if pos_end <= budget:
            return
        sel = SELECTORS[selector]
        lc = self.pool["layers"]
        k = lc["k"][:, slot, :pos_end]            # [L, S, H, D]
        v = lc["v"][:, slot, :pos_end]
        sp = lc["slot_pos"][:, slot, :pos_end]    # [L, S]

        def one(k_l, v_l, sp_l):
            nk, nv_, kept = sel(k_l[None], v_l[None], budget=budget,
                                pos=sp_l)
            return nk[0], nv_[0], kept[0]

        nk, nv_, kept = jax.vmap(one)(k, v, sp)   # [L,budget,...]
        s_full = lc["k"].shape[2]
        pad = s_full - budget
        nk = jnp.pad(nk, ((0, 0), (0, pad), (0, 0), (0, 0)))
        nv_ = jnp.pad(nv_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        nsp = jnp.pad(kept.astype(jnp.int32), ((0, 0), (0, pad)),
                      constant_values=-1)
        self.pool = dict(self.pool, layers=dict(
            lc,
            k=lc["k"].at[:, slot].set(nk.astype(lc["k"].dtype)),
            v=lc["v"].at[:, slot].set(nv_.astype(lc["v"].dtype)),
            slot_pos=lc["slot_pos"].at[:, slot].set(nsp)))

    # ------------------------------------------------------------- decode --
    def _decode_iteration(self, reqs: List[Request]) -> None:
        """One decode iteration through the pluggable decoder hooks.

        Decode-phase slots are GROUPED by each request's resolved strategy
        (``Request.decoder`` or the engine default) and each group's
        decoder runs once over its whole group -- batched speculative runs
        every speculative slot per jitted draft/verify call. Decoders run
        the forward pass(es) and slot bookkeeping and may emit MULTIPLE
        tokens per request per iteration (speculative); the engine applies
        request bookkeeping and stop conditions (eos emitted mid-block
        truncates the block: nothing is appended past DONE).

        Each group is charged its TRUE virtual-clock cost: the group's
        decoder may report one via ``_iter_decode_cost`` (speculative's
        block-verify + amortized draft steps, early-exit's executed-layer
        fraction); otherwise the group pays one plain batched decode step.
        Costs sum into the iteration's total.
        """
        groups: Dict[str, List[Request]] = {}
        for r in reqs:
            name, _ = self._resolve_decoder(r.decoder)
            groups.setdefault(name, []).append(r)
        total_cost = 0.0
        emitted_all: Dict[int, List[int]] = {}
        for name, group in groups.items():
            dec = self._decoders[name]
            self._iter_decode_cost = None
            if self.profiler.enabled:
                self.profiler.site_begin(f"decode:{name}", rows=len(group))
                self.profiler.count("decode_rows", len(group))
            emitted_all.update(dec.engine_decode(self, group))
            if self._iter_decode_cost is None:
                ctx = float(np.mean([self.slot_pos[r._slot] for r in group]))
                cost = self.ec.cost.decode_step_time(len(group), ctx)
            else:
                cost = self._iter_decode_cost
            if self.profiler.enabled:
                self.profiler.site_end(f"decode:{name}")
            total_cost += cost
            self.group_costs[name] = self.group_costs.get(name, 0.0) + cost
            if self.tracer.enabled:
                # one lane slice per decoder group per iteration: where
                # the virtual decode cost of a mixed fleet actually goes
                self.tracer.slice(f"decode:{name}", self.clock, cost,
                                  replica=self.trace_replica,
                                  batch=len(group))
        self._iter_decode_cost = total_cost
        for r in reqs:
            for tok in emitted_all.get(r._slot, ()):
                r.generated.append(tok)
                r.served_tokens += 1
                if r.is_finished() or tok == self.ec.eos_id:
                    r.state = State.DONE
                    break

    # --------------------------------------------------------------- step --
    def step(self) -> bool:
        """One scheduler iteration. Returns False when fully idle."""
        prof = self.profiler
        if prof.enabled:
            prof.site_begin("engine_step", it=self.iters)
            prof.site_begin("schedule")
        self.running = [r for r in self.running if r.state != State.DONE]
        visible = [r for r in self.waiting if r.arrival <= self.clock]
        plan = self.sched.plan(visible, self.running)
        # decode only requests whose KV is resident AND ready: an imported
        # request waits out its modeled transfer (``_ready_at``) first, a
        # MIGRATING request is frozen until export completes or cancels
        decode_reqs = [r for r in plan.decode if r.state == State.DECODE
                       and getattr(r, "_ready_at", 0.0) <= self.clock]
        if prof.enabled:
            prof.site_end("schedule")
        if not plan.prefill and not decode_reqs:
            if prof.enabled:
                prof.site_drop("engine_step")     # no work: not a step
            future = [r.arrival for r in self.waiting
                      if r.arrival > self.clock]
            future += [r._ready_at for r in self.running
                       if r.state == State.DECODE
                       and getattr(r, "_ready_at", 0.0) > self.clock]
            if future:                  # idle until arrival / KV readiness
                self.clock = min(future)
                return True
            return False
        self._iter_visual_tokens = 0
        self._iter_transfer_cost = 0.0    # shared-prefix-tier installs
        for req, n in plan.prefill:
            self._do_prefill_chunk(req, n)
        self._iter_decode_cost = 0.0      # summed per strategy group
        if decode_reqs:
            self._decode_iteration(decode_reqs)
        # virtual clock
        vt0 = self.clock
        dt = self.ec.cost.prefill_time(plan.prefill_tokens
                                       + self._iter_visual_tokens)
        dt += self._iter_decode_cost + self._iter_transfer_cost
        self.clock += dt
        self.iters += 1
        if self.tracer.enabled:
            self.tracer.slice("engine_step", vt0, dt,
                              replica=self.trace_replica,
                              prefill_tokens=plan.prefill_tokens,
                              decode_batch=len(decode_reqs))
            for r in decode_reqs:
                self.tracer.slice("decode_step", vt0, dt,
                                  replica=self.trace_replica,
                                  slot=r._slot, rid=r.rid)
        # stamp times & retire
        if prof.enabled:
            prof.site_begin("retire")
        seen, stampable = set(), []
        for r in self.running + [r for r, _ in plan.prefill]:
            if id(r) not in seen:
                seen.add(id(r))
                stampable.append(r)
        for r in stampable:
            if getattr(r, "_needs_ttft", False):
                r.first_token_time = self.clock
                r._needs_ttft = False
                if self.tracer.enabled:
                    self.tracer.instant("first_token", r.rid,
                                        replica=self.trace_replica,
                                        vt=self.clock)
            if r.state == State.DONE and r.finish_time is None:
                r.finish_time = self.clock
                self.finished.append(r)
                self._release_request(r)
                if self.tracer.enabled:
                    self.tracer.span_end("request", r.rid,
                                         replica=self.trace_replica,
                                         vt=self.clock,
                                         tokens=len(r.generated))
        self.running = [r for r in self.running if r.state != State.DONE]
        if prof.enabled:
            prof.site_end("retire")
        if self.sanitize:
            self._sanitize_check(f"Engine.step (iter {self.iters})")
        if prof.enabled:
            prof.site_end("engine_step")
        return True

    def run(self, max_iters: int = 100000) -> Dict:
        it = 0
        while self.step():
            it += 1
            if it >= max_iters:
                break
        out = summarize(self.finished)
        out["iterations"] = self.iters
        out["virtual_time_s"] = self.clock
        if self.ec.prefix_cache:
            out["prefix_hit_tokens"] = self.prefix_hit_tokens
            out["prefix_token_hit_rate"] = (
                self.prefix_hit_tokens / max(1, self.prefix_total_tokens))
        return out
