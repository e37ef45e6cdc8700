"""repro.analysis: rule fixtures, waivers, baseline, mutation tests.

Each rule family gets small inline fixture snippets (linted via
``analyze_source`` at a synthetic repo-relative path, so the path-based
scoping is exercised too), plus MUTATION tests over the real tree: the
acceptance bar is that deleting any single release call in
``Engine._release_request`` (or adding a ``repro.core`` import to an
example) flips the analyzer from clean to failing.
"""
import ast
import textwrap
from types import SimpleNamespace

import pytest

from repro.analysis import (Baseline, Finding, analyze_source,
                            check_engine_conservation,
                            check_server_conservation, parse_waivers,
                            run_analysis, select_rules)
from repro.analysis.cfg import ENTRY, EXIT, build_cfg, function_defs
from repro.analysis.findings import fence_lines

ENGINE_PATH = "src/repro/core/serving/engine.py"


def lint(src, path, rules=None):
    return analyze_source(textwrap.dedent(src), path, rules=rules)


def rules_of(findings):
    return [f.rule for f in findings]


# ----------------------------------------------------------- registry --
def test_select_rules_all_families_present():
    rules = select_rules("all")
    fams = {r.family for r in rules.values()}
    assert {"L", "R", "A", "K"} <= fams


def test_select_rules_by_family_and_id():
    assert set(select_rules(["L"])) == {"L001", "L002", "L003"}
    assert set(select_rules(["R002", "A"])) == {
        "R002", "A001", "A002", "A003"}
    with pytest.raises(ValueError):
        select_rules(["Z999"])


# ----------------------------------------------------------- L-rules --
CORE_IMPORT = """
    from repro.core.serving import Engine
    """


def test_l001_core_import_outside_src_flagged():
    fs = lint(CORE_IMPORT, "examples/demo.py", rules=["L001"])
    assert rules_of(fs) == ["L001"]


def test_l001_core_import_inside_src_and_tests_ok():
    for path in ("src/repro/api/lvlm.py", "tests/test_x.py"):
        assert lint(CORE_IMPORT, path, rules=["L001"]) == []


def test_l001_waiver_on_line_above_suppresses():
    src = """
    # analysis: allow L001 (micro-bench)
    from repro.core.serving import Engine
    """
    assert lint(src, "benchmarks/bench_x.py", rules=["L001"]) == []


def test_waiver_spans_comment_block_to_next_code_line():
    src = """
    # analysis: allow L001 (micro-bench: long justification that
    # continues on a second comment line before the import)
    from repro.core.kv_cache.budget import uniform_budgets
    """
    assert lint(src, "benchmarks/bench_x.py", rules=["L001"]) == []


def test_l002_engineconfig_compression_mutation_flagged():
    src = """
    from repro.api import EngineConfig
    cfg = EngineConfig(max_batch=2)
    cfg.compression = "framefusion-0.25"
    """
    fs = lint(src, "examples/demo.py", rules=["L002"])
    assert rules_of(fs) == ["L002"]


def test_l002_per_request_compression_not_flagged():
    # Request.compression is the sanctioned per-request knob (PR 5)
    src = """
    for r in reqs:
        r.compression = presets[i % len(presets)]
    """
    assert lint(src, "examples/demo.py", rules=["L002"]) == []


def test_l003_engine_construction_outside_src_flagged():
    src = """
    eng = Engine(model, params, cfg)
    """
    fs = lint(src, "scripts/run.py", rules=["L003"])
    assert rules_of(fs) == ["L003"]
    assert lint(src, "src/repro/api/lvlm.py", rules=["L003"]) == []


# ----------------------------------------------------------- R-rules --
def test_r002_acquire_with_unconditional_handoff_ok():
    src = """
    class E:
        def bind(self, req):
            slot = self._free_slot()
            req._slot = slot
            self.slot_req[slot] = req
    """
    assert lint(src, ENGINE_PATH, rules=["R002"]) == []


def test_r002_early_return_leaks_slot():
    src = """
    class E:
        def bind(self, req):
            slot = self._free_slot()
            if req.cancelled:
                return
            self.slot_req[slot] = req
    """
    fs = lint(src, ENGINE_PATH, rules=["R002"])
    assert rules_of(fs) == ["R002"]
    assert "slot" in fs[0].message


def test_r002_release_on_every_branch_ok():
    src = """
    class E:
        def bind(self, req):
            slot = self._free_slot()
            if req.cancelled:
                self._release_request(req)
                return
            self.slot_req[slot] = req
    """
    assert lint(src, ENGINE_PATH, rules=["R002"]) == []


def test_r002_exception_path_through_handler():
    # handler releases; fall-through handoff: both paths covered
    src = """
    class E:
        def bind(self, req):
            slot = self._free_slot()
            try:
                self.prefill(req)
            except RuntimeError:
                self._release_request(req)
                raise
            self.slot_req[slot] = req
    """
    assert lint(src, ENGINE_PATH, rules=["R002"]) == []


def test_r003_module_level_pairing():
    acquire_only = """
    class S:
        def register(self, rid, stream):
            self._streams[rid] = stream
    """
    fs = lint(acquire_only, "src/repro/serving/server.py", rules=["R003"])
    assert rules_of(fs) == ["R003"]
    paired = acquire_only + """
        def drop(self, rid):
            self._streams.pop(rid, None)
    """
    assert lint(paired, "src/repro/serving/server.py",
                rules=["R003"]) == []


# ------------------------------------------- R mutation (real tree) --
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(path):
    with open(os.path.join(ROOT, path), encoding="utf-8") as f:
        return f.read()


def _neutralize(src, needle):
    """Replace the first line containing ``needle`` with ``pass`` at the
    same indentation (keeps the mutant syntactically valid)."""
    lines = src.splitlines(keepends=True)
    for i, line in enumerate(lines):
        if needle in line:
            indent = line[:len(line) - len(line.lstrip())]
            lines[i] = indent + "pass\n"
            return "".join(lines)
    raise AssertionError(f"needle not found: {needle!r}")


def test_real_engine_is_clean_under_r_rules():
    src = _read("src/repro/core/serving/engine.py")
    assert lint(src, ENGINE_PATH, rules=["R"]) == []


@pytest.mark.parametrize("needle,action", [
    ("self.slot_req[slot] = None", "slot-unbind"),
    ("release(slot)", "draft-row release"),
    ("r._prefix_pin = None", "prefix-pin clear"),
])
def test_deleting_release_call_trips_r001(needle, action):
    src = _read("src/repro/core/serving/engine.py")
    mutant = _neutralize(src, needle)
    fs = lint(mutant, ENGINE_PATH, rules=["R001"])
    assert any(f.rule == "R001" and action in f.message for f in fs), fs


def test_deleting_pin_decrement_trips_r001():
    # the decrement action matches either the re-store or the pop;
    # both must go for the finding to fire
    src = _read("src/repro/core/serving/engine.py")
    mutant = _neutralize(src, "self._prefix_pins[key] = n")
    mutant = _neutralize(mutant, "self._prefix_pins.pop(key, None)")
    fs = lint(mutant, ENGINE_PATH, rules=["R001"])
    assert any("decrement" in f.message for f in fs), fs


@pytest.mark.parametrize("needle,action", [
    ("ticket = self._exports.pop(rid)", "export-ticket pop"),
    ("self.running.remove(req)", "running-list removal"),
    ('self.slot_req[ticket["slot"]] = None', "source-slot unbind"),
])
def test_deleting_migration_source_release_trips_r001(needle, action):
    """The KV-migration source release (complete_export) is R001-pinned:
    deleting any one of its release actions -- ticket pop, running-list
    removal, source-slot unbind -- must flip the analyzer."""
    src = _read("src/repro/core/serving/engine.py")
    mutant = _neutralize(src, needle)
    fs = lint(mutant, ENGINE_PATH, rules=["R001"])
    assert any(f.rule == "R001" and "complete_export" in f.message
               and action in f.message for f in fs), fs


CONTROLLER_PATH = "src/repro/control/controller.py"


def test_real_controller_is_clean_under_r_rules():
    src = _read(CONTROLLER_PATH)
    assert lint(src, CONTROLLER_PATH, rules=["R"]) == []


@pytest.mark.parametrize("needle,action", [
    ("req.compression = orig_comp", "preferred-compression restore"),
    ("req.decoder = orig_dec", "preferred-decoder restore"),
])
def test_deleting_controller_revert_restore_trips_r001(needle, action):
    """The controller's revert() is R001-pinned like _release_request:
    deleting any single field restore leaves a request permanently
    degraded after pressure clears, and must flip the analyzer."""
    src = _read(CONTROLLER_PATH)
    mutant = _neutralize(src, needle)
    fs = lint(mutant, CONTROLLER_PATH, rules=["R001"])
    assert any(f.rule == "R001" and "revert" in f.message
               and action in f.message for f in fs), fs


def test_deleting_controller_nv_invalidation_trips_r001():
    # "req.nv_compressed = None" appears in _apply_fields AND revert;
    # only revert's copy is R001-pinned, so neutralize both (first call
    # hits _apply_fields, second hits revert)
    src = _read(CONTROLLER_PATH)
    mutant = _neutralize(src, "req.nv_compressed = None")
    mutant = _neutralize(mutant, "req.nv_compressed = None")
    fs = lint(mutant, CONTROLLER_PATH, rules=["R001"])
    assert any(f.rule == "R001" and "revert" in f.message
               and "stamped-count invalidation" in f.message
               for f in fs), fs


def test_deleting_controller_override_pops_trips_r001_and_r003():
    # the pop line is identical in commit() and revert(); removing both
    # must trip R001 for each release function AND R003 (the module no
    # longer releases the control_override resource at all)
    src = _read(CONTROLLER_PATH)
    mutant = _neutralize(src, "self._overrides.pop(req.rid, None)")
    mutant = _neutralize(mutant, "self._overrides.pop(req.rid, None)")
    fs = lint(mutant, CONTROLLER_PATH, rules=["R001", "R003"])
    r001_funcs = {f.message for f in fs if f.rule == "R001"}
    assert any("commit" in m for m in r001_funcs), fs
    assert any("revert" in m for m in r001_funcs), fs
    assert any(f.rule == "R003" and "control_override" in f.message
               for f in fs), fs


def test_deleting_slot_handoff_trips_r002():
    src = _read("src/repro/core/serving/engine.py")
    mutant = _neutralize(src, "self.slot_req[slot] = req")
    fs = lint(mutant, ENGINE_PATH, rules=["R002"])
    assert any(f.rule == "R002" and "`slot`" in f.message for f in fs), fs


def test_adding_core_import_to_example_trips_l001():
    src = _read("examples/stream_video.py")
    assert lint(src, "examples/stream_video.py", rules=["L001"]) == []
    mutant = src + "\nfrom repro.core.serving import Engine\n"
    fs = lint(mutant, "examples/stream_video.py", rules=["L001"])
    assert rules_of(fs) == ["L001"]


# ----------------------------------------------------------- A-rules --
def test_a001_blocking_sleep_in_async():
    src = """
    import time
    async def pump(self):
        time.sleep(0.1)
    """
    fs = lint(src, "src/repro/serving/server.py", rules=["A001"])
    assert rules_of(fs) == ["A001"]


def test_a001_from_import_alias_and_sync_ok():
    flagged = """
    from time import sleep as zzz
    async def pump(self):
        zzz(0.1)
    """
    assert rules_of(lint(flagged, "src/x.py", rules=["A001"])) == ["A001"]
    ok = """
    import time, asyncio
    def sync_fn():
        time.sleep(0.1)
    async def pump(self):
        await asyncio.sleep(0.1)
    """
    assert lint(ok, "src/x.py", rules=["A001"]) == []


A002_HAZARD = """
    class S:
        async def pump(self):
            if self._streams:
                await self.tick()
                self._streams.pop(1, None)
    """


def test_a002_await_spanning_mutation_flagged():
    fs = lint(A002_HAZARD, "src/repro/serving/server.py", rules=["A002"])
    assert rules_of(fs) == ["A002"]
    assert "_streams" in fs[0].message


def test_a002_fence_comment_suppresses():
    fenced = A002_HAZARD.replace(
        "self._streams.pop(1, None)",
        "# analysis: atomic-step (pop of own key is idempotent)\n"
        "            self._streams.pop(1, None)")
    assert lint(fenced, "src/repro/serving/server.py",
                rules=["A002"]) == []


def test_a002_mutation_before_await_ok():
    src = """
    class S:
        async def pump(self):
            self._streams.pop(1, None)
            await self.tick()
    """
    assert lint(src, "src/repro/serving/server.py", rules=["A002"]) == []


def test_a003_fire_and_forget_task():
    src = """
    import asyncio
    def kick(loop):
        asyncio.create_task(work())
    """
    fs = lint(src, "src/x.py", rules=["A003"])
    assert rules_of(fs) == ["A003"]
    kept = """
    import asyncio
    def kick(loop):
        t = asyncio.create_task(work())
        return t
    """
    assert lint(kept, "src/x.py", rules=["A003"]) == []


# ----------------------------------------------------------- K-rules --
KERNEL_OK = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...].astype(o_ref.dtype)

    def run(x):
        return pl.pallas_call(
            kern,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
        )(x)
    """

KPATH = "src/repro/kernels/demo.py"


def test_kernel_fixture_clean():
    assert lint(KERNEL_OK, KPATH, rules=["K"]) == []


def test_k_rules_only_apply_to_kernel_paths():
    bad = KERNEL_OK.replace("lambda i:", "lambda i, j:")
    assert lint(bad, "src/repro/serving/server.py", rules=["K"]) == []
    assert rules_of(lint(bad, "src/attn_kernel.py", rules=["K001"])) \
        == ["K001", "K001"]


def test_k001_index_map_arity():
    bad = KERNEL_OK.replace(
        "in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))]",
        "in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, 0))]")
    fs = lint(bad, KPATH, rules=["K001"])
    assert rules_of(fs) == ["K001"]


def test_k001_defaulted_closure_params_ignored():
    ok = KERNEL_OK.replace(
        "in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))]",
        "in_specs=[pl.BlockSpec((8, 128), lambda i, g=2: (i, g))]")
    assert lint(ok, KPATH, rules=["K001"]) == []


def test_k002_kernel_signature_mismatch():
    bad = KERNEL_OK.replace("def kern(x_ref, o_ref):",
                            "def kern(x_ref, y_ref, o_ref):")
    bad = bad.replace("o_ref[...] = x_ref[...]",
                      "o_ref[...] = x_ref[...]")
    fs = lint(bad, KPATH, rules=["K002"])
    assert rules_of(fs) == ["K002"]


def test_k003_partial_tile_divisibility():
    bad = KERNEL_OK.replace("(32, 128)", "(33, 128)")
    fs = lint(bad, KPATH, rules=["K003"])
    assert rules_of(fs) == ["K003"]
    assert "33" in fs[0].message


def test_k004_store_without_astype():
    bad = KERNEL_OK.replace(
        "o_ref[...] = x_ref[...].astype(o_ref.dtype)",
        "o_ref[...] = x_ref[...] * 2.0")
    fs = lint(bad, KPATH, rules=["K004"])
    assert rules_of(fs) == ["K004"]


# ----------------------------------------------------------- O-rules --
SERVER_PATH = "src/repro/serving/server.py"

SPAN_OK = """
    class S:
        async def _admit(self, stream):
            if self.tracer.enabled:
                self.tracer.span_begin("admission_wait", 1)
            try:
                ok = await self.admission.admit(stream.request)
            except BaseException:
                if self.tracer.enabled:
                    self.tracer.span_abort(1)
                raise
            if not ok:
                if self.tracer.enabled:
                    self.tracer.span_end("admission_wait", 1)
                return
            if self.tracer.enabled:
                self.tracer.span_end("admission_wait", 1)
            self._wake.set()
    """


def test_o001_guarded_span_pairing_clean():
    """The `if tracer.enabled:` guard idiom pairs on every path,
    including the exception and retraction paths."""
    assert lint(SPAN_OK, SERVER_PATH, rules=["O001"]) == []


def test_o001_leaky_return_path_flagged():
    # drop the close on the not-admitted early return: that path now
    # exits with the span open
    bad = SPAN_OK.replace(
        """            if not ok:
                if self.tracer.enabled:
                    self.tracer.span_end("admission_wait", 1)
                return""",
        """            if not ok:
                return""")
    fs = lint(bad, SERVER_PATH, rules=["O001"])
    assert rules_of(fs) == ["O001", "O001"]     # guard header + call site
    assert "orphan span" in fs[0].message


def test_o001_module_pairing_for_engine_spans():
    src = """
    class Engine:
        def submit(self, req):
            self.tracer.span_begin("request", req.rid)

        def step(self):
            self.tracer.span_end("request", 1)
    """
    assert lint(src, ENGINE_PATH, rules=["O001"]) == []
    bad = src.replace('self.tracer.span_end("request", 1)', "pass")
    fs = lint(bad, ENGINE_PATH, rules=["O001"])
    assert rules_of(fs) == ["O001"]
    assert "no span_end/span_abort site" in fs[0].message


def test_renaming_server_span_closes_trips_o001():
    """Real-tree mutation: neutering every close in the server leaves
    _admit/import_stream opening spans no path ever closes."""
    src = _read("src/repro/serving/server.py")
    mutant = (src.replace("span_end(", "span_noop(")
              .replace("span_abort(", "span_noop("))
    fs = lint(mutant, SERVER_PATH, rules=["O001"])
    assert fs and all(f.rule == "O001" for f in fs), fs


def test_renaming_engine_span_closes_trips_o001():
    src = _read("src/repro/core/serving/engine.py")
    mutant = (src.replace("span_end(", "span_noop(")
              .replace("span_abort(", "span_noop("))
    fs = lint(mutant, ENGINE_PATH, rules=["O001"])
    assert fs and all(f.rule == "O001" for f in fs), fs


@pytest.mark.parametrize("call,action", [
    ("span_abort(", "trace span close on abort"),
    ("span_end(", "request-span close at retire"),
])
def test_deleting_engine_span_close_trips_r001(call, action):
    """The R-table pins the specific closes: Engine.abort must
    span_abort, Engine.step must span_end at retire."""
    src = _read("src/repro/core/serving/engine.py")
    mutant = src.replace(call, "span_noop(")
    fs = lint(mutant, ENGINE_PATH, rules=["R001"])
    assert any(f.rule == "R001" and action in f.message for f in fs), fs


O002_KERNEL = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        tracer.instant("inner", 0)
        o_ref[...] = x_ref[...].astype(o_ref.dtype)

    def run(x, tracer):
        tracer.span_begin("run", 0)
        out = pl.pallas_call(
            kern,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
        )(x)
        tracer.span_end("run", 0)
        return out
    """


def test_o002_kernel_emission_flagged():
    fs = lint(O002_KERNEL, KPATH, rules=["O002"])
    assert rules_of(fs) == ["O002"]
    assert "trace time" in fs[0].message


PROFILE_OK = """
    class Engine:
        def _decode_iteration(self):
            if self.profiler.enabled:
                self.profiler.site_begin("decode:greedy")
            cost = self._launch()
            if cost is None:
                if self.profiler.enabled:
                    self.profiler.site_end("decode:greedy")
                return 0.0
            if self.profiler.enabled:
                self.profiler.site_end("decode:greedy")
            return cost
    """


def test_o003_guarded_site_pairing_clean():
    """Profiler sites close on every CFG path (per-function pairing:
    unlike trace spans, a site never crosses function boundaries)."""
    assert lint(PROFILE_OK, ENGINE_PATH, rules=["O003"]) == []


def test_o003_leaky_site_flagged():
    bad = PROFILE_OK.replace(
        """            if cost is None:
                if self.profiler.enabled:
                    self.profiler.site_end("decode:greedy")
                return 0.0""",
        """            if cost is None:
                return 0.0""")
    fs = lint(bad, ENGINE_PATH, rules=["O003"])
    assert rules_of(fs) == ["O003", "O003"]     # guard header + call site
    assert "self/total attribution" in fs[0].message


PROFILE_PHASES = """
    class Engine:
        def step(self):
            if self.profiler.enabled:
                self.profiler.site_begin("engine_step")
            plan = self._plan()
            if not plan:
                if self.profiler.enabled:
                    self.profiler.site_drop("engine_step")
                return False
            if self.profiler.enabled:
                self.profiler.wait_begin("wait:decode")
            self._pull()
            if self.profiler.enabled:
                self.profiler.wait_end("wait:decode")
            if self.profiler.enabled:
                self.profiler.site_begin("retire")
            self._retire()
            if self.profiler.enabled:
                self.profiler.site_end("retire")
            if self.profiler.enabled:
                self.profiler.site_end("engine_step")
            return True
    """


@pytest.mark.parametrize("close", [
    None, 'self.profiler.site_end("retire")',
    'self.profiler.wait_end("wait:decode")',
    'self.profiler.site_drop("engine_step")'])
def test_o003_pairs_each_site_by_name(close):
    """A site or wait whose own close is missing is flagged even where
    another site's close follows it on every path."""
    if close is None:
        assert lint(PROFILE_PHASES, ENGINE_PATH, rules=["O003"]) == []
        return
    bad = PROFILE_PHASES.replace(close, "pass")
    fs = lint(bad, ENGINE_PATH, rules=["O003"])
    assert rules_of(fs) == ["O003", "O003"]     # guard header + call site


def test_renaming_engine_site_closes_trips_o003():
    """Real-tree mutation: neutering every site_end in the engine leaves
    the prefill/decode/compress sites open on every path."""
    src = _read("src/repro/core/serving/engine.py")
    mutant = src.replace("site_end(", "site_noop(")
    fs = lint(mutant, ENGINE_PATH, rules=["O003"])
    assert fs and all(f.rule == "O003" for f in fs), fs


def test_o002_host_wrapper_emission_clean():
    ok = O002_KERNEL.replace('    tracer.instant("inner", 0)\n', '')
    assert lint(ok, KPATH, rules=["O002"]) == []


def test_o002_generic_names_need_a_tracer_object():
    # jax.lax.slice inside a kernel shares a name with Tracer.slice;
    # only calls on a tracer object count
    ok = O002_KERNEL.replace(
        'tracer.instant("inner", 0)',
        'y = jax.lax.slice(x_ref[...], (0, 0), (4, 4))')
    assert lint(ok, KPATH, rules=["O002"]) == []


# ------------------------------------------------- waivers / baseline --
def test_syntax_error_reports_e000():
    fs = analyze_source("def broken(:\n", "src/x.py")
    assert rules_of(fs) == ["E000"]


def test_parse_waivers_multiple_rules():
    waived = parse_waivers(
        "x = 1  # analysis: allow L001, A002 (legacy)\n")
    assert waived[1] == {"L001", "A002"}


def test_fence_lines_cover_next_code_line():
    src = ("# analysis: atomic-step (safe:\n"
           "# own entry only)\n"
           "self._waiters.remove(e)\n")
    assert fence_lines(src) >= {1, 2, 3}


def test_baseline_roundtrip_and_line_slack(tmp_path):
    f = Finding(path="a.py", line=10, rule="L001", severity="error",
                message="m")
    bl = Baseline([f])
    p = tmp_path / "baseline.json"
    bl.save(str(p))
    loaded = Baseline.load(str(p))
    near = Finding(path="a.py", line=15, rule="L001", severity="error",
                   message="moved")
    far = Finding(path="a.py", line=40, rule="L001", severity="error",
                  message="new")
    assert loaded.is_baselined(near)
    assert loaded.filter([near, far]) == [far]


# ------------------------------------------------------------- CFG --
def test_cfg_loop_break_and_finally_paths():
    src = textwrap.dedent("""
    def f(xs):
        acc = 0
        for x in xs:
            if x < 0:
                break
            acc += x
        try:
            return acc
        finally:
            log(acc)
    """)
    fn = next(iter(function_defs(ast.parse(src))))
    g = build_cfg(fn)
    stmts = {s.lineno: s for s in g.succ if not isinstance(s, str)}
    # the finally body (`log(acc)`, line 11) is on every path to EXIT:
    # avoiding it disconnects the function from its exit
    assert not g.path_avoiding(ENTRY, EXIT, {stmts[11]})
    assert g.path_avoiding(ENTRY, EXIT, set())


# ------------------------------------------------ whole-tree contract --
def test_repo_tree_is_clean():
    """The committed tree has zero non-baselined findings (what CI runs
    as `python -m repro.analysis --fail-on-regression`)."""
    report = run_analysis()
    assert report.ok, report.render()
    assert report.files_checked > 50


# ----------------------------------------------------------- sanitizer --
def _fake_engine(n_slots=2, cache_len=32):
    from repro.core.serving.request import State
    req = SimpleNamespace(rid=7, state=State.DECODE, _slot=0,
                          _prefix_pin=None)
    eng = SimpleNamespace(
        running=[req], waiting=[], slot_req=[req] + [None] * (n_slots - 1),
        slot_pos=[4] + [0] * (n_slots - 1),
        ec=SimpleNamespace(cache_len=cache_len),
        _decoders={}, _prefix_pins={}, _prefix={},
        kv_committed_tokens=lambda include_waiting=True: 4,
        kv_request_tokens=lambda r: 4)
    return eng, req


def test_sanitizer_clean_fake_engine():
    eng, _ = _fake_engine()
    assert check_engine_conservation(eng) == []


def test_sanitizer_detects_kv_drift():
    eng, _ = _fake_engine()
    eng.kv_committed_tokens = lambda include_waiting=True: 9
    assert any("kv_committed" in p
               for p in check_engine_conservation(eng))


def test_sanitizer_detects_slot_bound_to_done_request():
    from repro.core.serving.request import State
    eng, req = _fake_engine()
    req.state = State.DONE
    eng.running = []
    eng.kv_committed_tokens = lambda include_waiting=True: 0
    assert any("slot leak" in p for p in check_engine_conservation(eng))


def test_sanitizer_detects_draft_row_leak():
    eng, _ = _fake_engine()
    eng._decoders = {"speculative": SimpleNamespace(
        bound_slots=lambda: {0, 1})}      # slot 1 is free in slot_req
    assert any("draft-row leak" in p
               for p in check_engine_conservation(eng))


def test_sanitizer_detects_pin_leak_both_directions():
    eng, req = _fake_engine()
    key = ("none", (1, 2, 3))
    # counted pin with no live holder
    eng._prefix_pins = {key: 1}
    eng._prefix = {key: ()}
    assert any("pin leak" in p for p in check_engine_conservation(eng))
    # live holder the engine no longer counts
    eng._prefix_pins = {}
    req._prefix_pin = key
    assert any("no longer counts" in p
               for p in check_engine_conservation(eng))


def test_sanitizer_server_orphan_stream():
    eng, _ = _fake_engine()
    server = SimpleNamespace(engine=eng, _streams={})
    assert any("no registered stream" in p
               for p in check_server_conservation(server))
    server._streams = {7: SimpleNamespace(aborted=False)}
    assert check_server_conservation(server) == []
