"""tpulint unit tests: one violating + one clean fixture per rule, pragma
suppression, baseline round-trip, CLI exit codes, and the --fix rewrites.

All in-memory via ``lint_source`` (stdlib-ast only, no jax in the tool) —
every test here is fast and tier-1."""

import json
import textwrap

import pytest

from deepspeed_tpu.tools.tpulint import (
    Finding,
    lint_source,
    load_baseline,
    new_findings,
    save_baseline,
)
from deepspeed_tpu.tools.tpulint.cli import main as cli_main


def _lint(src, path, rule, root="."):
    return lint_source(textwrap.dedent(src), path, root=root, rules=[rule])


def _ids(findings):
    return [f.rule for f in findings]


# ------------------------------------------------------ rule 1: layouts


def test_layout_shim_routing_flags_import():
    found = _lint(
        """
        from jax.experimental.layout import Format, Layout
        fmt = Format(Layout.AUTO)
        """, "deepspeed_tpu/inference/engine.py", "layout-shim-routing")
    # the import AND the aliased Layout.AUTO attribute use both flag
    assert _ids(found) == ["layout-shim-routing"] * 2
    assert found[0].line == 2
    assert found[0].fix == "layout-import"


def test_layout_shim_routing_flags_attribute_use():
    found = _lint(
        """
        import jax
        fmt = jax.experimental.layout.Format(None)
        """, "benchmarks/hf7b_decode.py", "layout-shim-routing")
    assert _ids(found) == ["layout-shim-routing"]


def test_layout_shim_routing_clean_in_layouts_and_via_shim():
    # the one allowed home
    assert _lint("from jax.experimental.layout import Format\n",
                 "deepspeed_tpu/utils/layouts.py",
                 "layout-shim-routing") == []
    # the blessed call sites
    assert _lint(
        """
        from deepspeed_tpu.utils.layouts import auto_input_format
        fmt = auto_input_format()
        """, "deepspeed_tpu/inference/engine.py", "layout-shim-routing") == []


# --------------------------------------------------- rule 2: jax_compat


def test_compat_shim_routing_flags_old_home_and_from_imports():
    found = _lint(
        """
        from jax.experimental.shard_map import shard_map
        from jax import shard_map as sm2
        from jax.lax import pcast
        """, "deepspeed_tpu/ops/pallas/sharded.py", "compat-shim-routing")
    assert _ids(found) == ["compat-shim-routing"] * 3
    assert found[0].fix == "shard-map-import"
    assert found[1].fix is None and found[2].fix is None


def test_compat_shim_routing_clean_attribute_spelling():
    # jax.shard_map / jax.lax.pcast ATTRIBUTES are the spellings the rule
    # allows
    assert _lint(
        """
        import jax
        f = jax.shard_map(lambda x: jax.lax.pcast(x, "data"), mesh=None)
        """, "deepspeed_tpu/ops/pallas/sharded.py", "compat-shim-routing") == []


# ----------------------------------------------------- rule 3: set_mesh


def test_no_set_mesh_flags_attribute_and_import():
    found = _lint(
        """
        import jax
        from jax.lax import axis_size
        with jax.set_mesh(None):
            pass
        """, "deepspeed_tpu/runtime/engine.py", "no-set-mesh")
    assert _ids(found) == ["no-set-mesh"] * 2


def test_no_set_mesh_pragma_and_clean():
    src = (
        "import jax\n"
        "with jax.set_mesh(None):  # tpulint: disable=no-set-mesh\n"
        "    pass\n")
    assert lint_source(src, "tests/unit/comm/test_x.py",
                       rules=["no-set-mesh"]) == []
    assert _lint(
        """
        import jax
        n = mesh.shape["data"]
        """, "deepspeed_tpu/runtime/engine.py", "no-set-mesh") == []


# -------------------------------------------- rule 4: manual-region purity


def test_manual_region_purity_flags_axis_index_in_region():
    found = _lint(
        """
        import jax

        def region(x):
            r = jax.lax.axis_index("data")
            return x + r

        f = jax.shard_map(region, mesh=None)
        """, "deepspeed_tpu/ops/pallas/new_kernel.py", "manual-region-purity")
    assert _ids(found) == ["manual-region-purity"]


def test_manual_region_purity_clean_sharded_arange_and_other_dirs():
    # shard identity from a sharded input: the portability idiom
    assert _lint(
        """
        import jax

        def region(x, shard_ids):
            return x + shard_ids[0]

        f = jax.shard_map(region, mesh=None)
        """, "deepspeed_tpu/ops/pallas/new_kernel.py",
        "manual-region-purity") == []
    # outside ops/pallas the rule does not apply (sequence/ring_attention
    # is governed by no-set-mesh + its own pragma instead)
    assert _lint(
        """
        import jax

        def region(x):
            return x + jax.lax.axis_index("sequence")

        f = jax.shard_map(region, mesh=None)
        """, "deepspeed_tpu/sequence/ring_attention.py",
        "manual-region-purity") == []


# ------------------------------------------------ rule 5: fault points


def test_host_only_fault_points_flags_traced_fault_point():
    found = _lint(
        """
        import jax
        from deepspeed_tpu.resilience.faults import fault_point

        @jax.jit
        def step(x):
            fault_point("device_put")
            return x
        """, "deepspeed_tpu/runtime/engine.py", "host-only-fault-points")
    assert _ids(found) == ["host-only-fault-points"]


def test_host_only_fault_points_flags_scan_body_via_fixpoint():
    found = _lint(
        """
        import jax
        from deepspeed_tpu.resilience.faults import fault_point

        def helper(x):
            fault_point("device_put")
            return x

        def body(carry, x):
            return helper(carry), x

        def run(xs):
            return jax.lax.scan(body, 0, xs)
        """, "deepspeed_tpu/runtime/engine.py", "host-only-fault-points")
    assert _ids(found) == ["host-only-fault-points"]


def test_host_only_fault_points_clean_on_host():
    assert _lint(
        """
        import jax
        from deepspeed_tpu.resilience.faults import fault_point

        def place(params):
            fault_point("param_placement")
            return jax.device_put(params)
        """, "deepspeed_tpu/runtime/engine.py", "host-only-fault-points") == []


def test_host_only_fault_points_flags_partial_chains():
    # both partial orientations reach the traced index:
    # jit(partial(fn, ...)) and partial(jit, ...)(fn)
    found = _lint(
        """
        import functools
        import jax
        from functools import partial
        from deepspeed_tpu.resilience.faults import fault_point

        def body_a(cfg, x):
            fault_point("device_put")
            return x

        def body_b(x):
            fault_point("device_put")
            return x

        f1 = jax.jit(partial(body_a, {}))
        f2 = functools.partial(jax.jit, donate_argnums=(0,))(body_b)
        """, "deepspeed_tpu/runtime/engine.py", "host-only-fault-points")
    assert _ids(found) == ["host-only-fault-points"] * 2


def test_host_only_fault_points_flags_decorator_alias():
    found = _lint(
        """
        import functools
        import jax
        from deepspeed_tpu.resilience.faults import fault_point

        step_jit = functools.partial(jax.jit, donate_argnums=(0,))
        my_jit = jax.jit

        @step_jit
        def step(state):
            fault_point("device_put")
            return state

        @my_jit
        def other(x):
            fault_point("device_put")
            return x
        """, "deepspeed_tpu/runtime/engine.py", "host-only-fault-points")
    assert _ids(found) == ["host-only-fault-points"] * 2


def test_host_only_fault_points_clean_host_side_partial():
    # partial of a HOST function stays host — no trace entry involved
    assert _lint(
        """
        import functools
        from deepspeed_tpu.resilience.faults import fault_point

        def stage(layer, params):
            fault_point("device_put")
            return params

        stage_l0 = functools.partial(stage, 0)
        loader = functools.partial(map, stage_l0)
        """, "deepspeed_tpu/runtime/engine.py", "host-only-fault-points") == []


# ---------------------------------------------- rule 6: hot-loop fetch


def test_no_hot_loop_fetch_flags_per_iteration_fetch():
    found = _lint(
        """
        import numpy as np
        import jax

        def decode_loop(progs, state, steps):
            outs = []
            for _ in range(steps):
                state, tok = progs["step"](state)
                outs.append(np.asarray(tok))
            return outs
        """, "deepspeed_tpu/inference/engine.py", "no-hot-loop-fetch")
    assert _ids(found) == ["no-hot-loop-fetch"]


def test_no_hot_loop_fetch_flags_block_until_ready():
    found = _lint(
        """
        def wait_all(refs):
            while refs:
                refs.pop().block_until_ready()
        """, "deepspeed_tpu/inference/speculative.py", "no-hot-loop-fetch")
    assert _ids(found) == ["no-hot-loop-fetch"]


def test_no_hot_loop_fetch_scoped_and_batched_clean():
    src = """
        import jax

        def decode_loop(progs, state, steps):
            toks = []
            for _ in range(steps):
                state, tok = progs["step"](state)
                toks.append(tok)
            return jax.device_get(toks)
        """
    # one batched fetch AFTER the loop: clean
    assert _lint(src, "deepspeed_tpu/inference/engine.py",
                 "no-hot-loop-fetch") == []
    # and the rule only governs the four engine hot-path files
    bad = """
        import numpy as np
        def f(xs):
            return [np.asarray(x) for x in xs]
        """
    assert _lint(bad, "deepspeed_tpu/checkpoint/ds_export.py",
                 "no-hot-loop-fetch") == []
    assert _lint(bad, "deepspeed_tpu/inference/capacity_scan.py",
                 "no-hot-loop-fetch") != []


# ------------------------------------------- rule 7: wallclock in traced


def test_no_wallclock_in_traced_flags_time_in_jit():
    found = _lint(
        """
        import time
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=0)
        def step(state):
            t = time.perf_counter()
            return state, t
        """, "deepspeed_tpu/telemetry/hub.py", "no-wallclock-in-traced")
    assert _ids(found) == ["no-wallclock-in-traced"]


def test_no_wallclock_in_traced_clean_on_host():
    assert _lint(
        """
        import time
        import jax

        @jax.jit
        def step(state):
            return state

        def timed(state):
            t0 = time.perf_counter()
            out = step(state)
            return out, time.perf_counter() - t0
        """, "deepspeed_tpu/telemetry/hub.py", "no-wallclock-in-traced") == []


# --------------------------------------------- rule 8: telemetry schema


@pytest.fixture
def schema_root(tmp_path):
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "telemetry.md").write_text(textwrap.dedent("""\
        # Telemetry

        Common fields: `ts`, `kind`, `step`.

        ### `train_step`
        Per-step metrics: `loss`, `grad_norm`.
        """))
    return str(tmp_path)


def test_telemetry_schema_sync_flags_unknown_kind_and_field(schema_root):
    found = lint_source(textwrap.dedent("""
        def report(hub, loss):
            hub.emit("train_step", loss=loss, new_field=1)
            hub.emit("mystery_kind", x=1)
        """), "deepspeed_tpu/telemetry/hub.py", root=schema_root,
        rules=["telemetry-schema-sync"])
    msgs = sorted(f.message for f in found)
    assert len(found) == 2
    assert "new_field" in msgs[1] and "mystery_kind" in msgs[0]


def test_telemetry_schema_sync_clean_documented_and_kwargs(schema_root):
    assert lint_source(textwrap.dedent("""
        def report(hub, loss, extra):
            hub.emit("train_step", loss=loss, grad_norm=0.0, step=1)
            hub.emit("train_step", **extra)
        """), "deepspeed_tpu/telemetry/hub.py", root=schema_root,
        rules=["telemetry-schema-sync"]) == []
    # tests/ are out of scope (they emit synthetic kinds on purpose)
    assert lint_source('hub.emit("synthetic", x=1)\n',
                       "tests/unit/test_hub.py", root=schema_root,
                       rules=["telemetry-schema-sync"]) == []


# ------------------------------------------------- rule 9: warn_once


def test_warn_once_discipline_flags_loop_warning():
    found = _lint(
        """
        from deepspeed_tpu.utils.logging import logger

        def retry(fn, n):
            for i in range(n):
                logger.warning("attempt %d failed", i)
        """, "deepspeed_tpu/resilience/retry2.py", "warn-once-discipline")
    assert _ids(found) == ["warn-once-discipline"]


def test_warn_once_discipline_clean_warn_once_and_outside_loop():
    assert _lint(
        """
        from deepspeed_tpu.utils.logging import logger, warn_once

        def retry(fn, n):
            for i in range(n):
                warn_once(("retry", fn), "retrying %s", fn)
            logger.warning("gave up")
        """, "deepspeed_tpu/resilience/retry2.py", "warn-once-discipline") == []


# ------------------------------------------------ rule 10: slow marks


def test_slow_mark_discipline_flags_each_indicator():
    src = """
        from tests.util.subproc_retry import run_pytest_retry

        def test_cached_decode_parity():
            pass

        def test_rotation_wrapper():
            run_pytest_retry("tests/unit/pipe", "k")

        def test_longctx():
            s = 131072
        """
    found = _lint(src, "tests/unit/inference/test_zoo.py",
                  "slow-mark-discipline")
    assert _ids(found) == ["slow-mark-discipline"] * 3


def test_slow_mark_discipline_clean_marked_and_small():
    assert _lint(
        """
        import pytest
        from tests.util.subproc_retry import run_pytest_retry

        @pytest.mark.slow
        def test_cached_decode_parity():
            run_pytest_retry("tests/unit/pipe", "k")

        def test_small():
            s = 4096
        """, "tests/unit/inference/test_zoo.py", "slow-mark-discipline") == []
    # module-level pytestmark also counts
    assert _lint(
        """
        import pytest
        pytestmark = pytest.mark.slow

        def test_cached_decode_parity():
            pass
        """, "tests/unit/inference/test_zoo.py", "slow-mark-discipline") == []


# ------------------------------------ rule 12: raw-collective-discipline


def test_raw_collective_discipline_flags_import_and_call():
    found = _lint(
        """
        import jax
        from jax.lax import psum
        g = jax.lax.all_gather(x, "data")
        """, "deepspeed_tpu/inference/engine.py",
        "raw-collective-discipline")
    assert _ids(found) == ["raw-collective-discipline"] * 2
    assert "psum" in found[0].message
    assert "all_gather" in found[1].message


def test_raw_collective_discipline_clean_allowed_dirs_and_pragma():
    # ops/, runtime/, comm/ are the declared collective homes
    for path in ("deepspeed_tpu/ops/pallas/sharded.py",
                 "deepspeed_tpu/runtime/zero/partition.py",
                 "deepspeed_tpu/comm/comm.py"):
        assert _lint(
            """
            import jax
            g = jax.lax.psum(x, "data")
            """, path, "raw-collective-discipline") == []
    # non-collective lax is never the rule's business
    assert _lint(
        """
        import jax
        i = jax.lax.axis_index("pipe")
        """, "deepspeed_tpu/pipe/engine.py",
        "raw-collective-discipline") == []
    # the deliberate manual-region spelling: justification + pragma
    src = (
        "import jax\n"
        "# the rotation ring IS the wire format (manual region)\n"
        "# tpulint: disable-next-line=raw-collective-discipline\n"
        "y = jax.lax.ppermute(x, 'pipe', perm)\n")
    assert lint_source(src, "deepspeed_tpu/pipe/engine.py",
                       rules=["raw-collective-discipline"]) == []


# ----------------------------------------------------- pragmas (generic)


def test_pragma_same_line_next_line_and_wrong_rule():
    src = (
        "import jax\n"
        "a = jax.set_mesh  # tpulint: disable=no-set-mesh\n"
        "# tpulint: disable-next-line=no-set-mesh\n"
        "b = jax.set_mesh\n"
        "c = jax.set_mesh  # tpulint: disable=layout-shim-routing\n")
    found = lint_source(src, "deepspeed_tpu/x.py", rules=["no-set-mesh"])
    assert [f.line for f in found] == [5]  # wrong-rule pragma doesn't hide
    # audit mode sees everything
    found_all = lint_source(src, "deepspeed_tpu/x.py", rules=["no-set-mesh"],
                            respect_pragmas=False)
    assert [f.line for f in found_all] == [2, 4, 5]


def test_syntax_error_reported_not_raised():
    found = lint_source("def broken(:\n", "deepspeed_tpu/x.py")
    assert _ids(found) == ["syntax-error"]


# ----------------------------------------------------------- baseline


def test_baseline_round_trip_and_count_semantics(tmp_path):
    f1 = Finding("no-set-mesh", "a.py", 3, 0, "msg")
    f2 = Finding("no-set-mesh", "a.py", 9, 0, "msg")   # same key, 2nd hit
    f3 = Finding("no-set-mesh", "b.py", 1, 0, "msg")
    path = str(tmp_path / "base.json")
    save_baseline(path, [f1, f2])
    baseline = load_baseline(path)
    assert baseline == {"no-set-mesh|a.py|msg": 2}
    # both grandfathered, line drift irrelevant; b.py is new
    drifted = Finding("no-set-mesh", "a.py", 30, 0, "msg")
    assert new_findings([drifted, f2, f3], baseline) == [f3]
    # a third occurrence in a.py exceeds the count and reports
    assert new_findings([f1, f2, drifted], baseline) == [drifted]


# ---------------------------------------------------------------- CLI


def _write(tmp_path, name, text):
    p = tmp_path / name
    p.write_text(textwrap.dedent(text))
    return str(p)


def test_cli_exit_codes(tmp_path, capsys):
    clean = _write(tmp_path, "clean.py", "import jax\nx = 1\n")
    dirty = _write(tmp_path, "dirty.py",
                   "import jax\nm = jax.set_mesh\n")
    assert cli_main([clean, "--no-baseline"]) == 0
    assert cli_main([dirty, "--no-baseline"]) == 1
    out = capsys.readouterr().out
    assert "dirty.py:2" in out and "no-set-mesh" in out
    assert cli_main([str(tmp_path / "nope.py")]) == 2
    assert cli_main([dirty, "--select", "not-a-rule"]) == 2
    assert cli_main(["--list-rules"]) == 0
    listing = capsys.readouterr().out
    assert "no-set-mesh" in listing and "slow-mark-discipline" in listing


def test_cli_baseline_flow(tmp_path, capsys):
    dirty = _write(tmp_path, "dirty.py", "import jax\nm = jax.set_mesh\n")
    base = str(tmp_path / "base.json")
    assert cli_main([dirty, "--update-baseline", "--baseline", base]) == 0
    assert json.load(open(base))["findings"][0]["rule"] == "no-set-mesh"
    # grandfathered now
    assert cli_main([dirty, "--baseline", base]) == 0
    # a NEW occurrence of the same key still reports
    (tmp_path / "dirty.py").write_text(
        "import jax\nm = jax.set_mesh\nn = jax.set_mesh\n")
    assert cli_main([dirty, "--baseline", base]) == 1
    capsys.readouterr()


def test_cli_fix_shard_map_import(tmp_path, capsys):
    target = _write(tmp_path, "kernels.py", """\
        from jax.experimental.shard_map import shard_map

        def wrap(fn, mesh):
            return shard_map(fn, mesh=mesh, in_specs=None, out_specs=None)
        """)
    assert cli_main([target, "--fix", "--no-baseline"]) == 0
    text = open(target).read()
    assert "jax.experimental.shard_map" not in text
    assert "jax.shard_map(fn" in text
    assert "import jax" in text
    capsys.readouterr()


def test_cli_fix_layout_import(tmp_path, capsys):
    target = _write(tmp_path, "serve.py", """\
        from jax.experimental.layout import Format, Layout

        def fmts(n):
            return [Format(Layout.AUTO)] * n
        """)
    assert cli_main([target, "--fix", "--no-baseline"]) == 0
    text = open(target).read()
    assert "jax.experimental.layout" not in text
    assert "from deepspeed_tpu.utils.layouts import auto_input_format" in text
    assert "auto_input_format()" in text
    capsys.readouterr()


# ------------------------------------------------- --fix: warn-once


def _fake_repo(tmp_path, rel, text):
    """A minimal repo layout so find_root anchors at tmp_path and the
    fixed file lints under its deepspeed_tpu/ relpath."""
    (tmp_path / "docs").mkdir(exist_ok=True)
    target = tmp_path / rel
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(textwrap.dedent(text))
    return str(target)


def test_cli_fix_warn_once_round_trip(tmp_path, capsys):
    target = _fake_repo(tmp_path, "deepspeed_tpu/runtime/staging.py", """\
        from deepspeed_tpu.utils.logging import logger

        def stage_all(layers):
            for l in layers:
                logger.warning("stage failed for %s, retrying", l)
        """)
    assert cli_main([target, "--fix", "--no-baseline"]) == 0
    text = open(target).read()
    assert 'warn_once("stage failed for %s, retrying", ' \
           '"stage failed for %s, retrying", l)' in text
    assert "from deepspeed_tpu.utils.logging import logger, warn_once" \
        in text
    # fixed output parses and re-lints clean
    import ast as _ast
    _ast.parse(text)
    assert lint_source(text, "deepspeed_tpu/runtime/staging.py",
                       rules=["warn-once-discipline"]) == []
    capsys.readouterr()


def test_fix_warn_once_leaves_computed_messages(tmp_path, capsys):
    # a computed message has no safe literal key — report-only, no rewrite
    src = """\
        from deepspeed_tpu.utils.logging import logger

        def stage_all(layers):
            for l in layers:
                msg = "failed %s" % l
                logger.warning(msg)
        """
    target = _fake_repo(tmp_path, "deepspeed_tpu/runtime/staging.py", src)
    assert cli_main([target, "--fix", "--no-baseline"]) == 1
    assert open(target).read() == textwrap.dedent(src)
    found = lint_source(textwrap.dedent(src),
                        "deepspeed_tpu/runtime/staging.py",
                        rules=["warn-once-discipline"])
    assert [f.fix for f in found] == [None]
    capsys.readouterr()


def test_fix_warn_once_inserts_import_once(tmp_path, capsys):
    # no existing utils.logging import: one import line added per file,
    # even with two fixable calls
    target = _fake_repo(tmp_path, "deepspeed_tpu/runtime/staging.py", """\
        import logging

        logger = logging.getLogger(__name__)

        def stage_all(layers):
            for l in layers:
                logger.warning("stage failed")
                logger.warning("retry queued")
        """)
    assert cli_main([target, "--fix", "--no-baseline"]) == 0
    text = open(target).read()
    assert text.count(
        "from deepspeed_tpu.utils.logging import warn_once") == 1
    assert 'warn_once("stage failed", "stage failed")' in text
    assert 'warn_once("retry queued", "retry queued")' in text
    capsys.readouterr()


# ------------------------------------- rule 8b: telemetry append-only


@pytest.fixture
def snapshot_root(tmp_path):
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "telemetry.md").write_text(textwrap.dedent("""\
        # Telemetry

        ### `train_step`
        Per-step metrics: `loss`, `grad_norm`.

        ### `serving`
        Decode events: `tokens_per_s`.
        """))
    return tmp_path


def _snapshot(root, kinds):
    (root / "docs" / "telemetry_schema.json").write_text(
        json.dumps({"version": 1,
                    "kinds": {k: sorted(v) for k, v in kinds.items()}}))


_ANCHOR = "deepspeed_tpu/telemetry/hub.py"


def _append_only(root):
    return lint_source("x = 1\n", _ANCHOR, root=str(root),
                       rules=["telemetry-append-only"])


def test_telemetry_append_only_no_snapshot_is_bootstrap(snapshot_root):
    assert _append_only(snapshot_root) == []


def test_telemetry_append_only_clean_when_in_sync(snapshot_root):
    from deepspeed_tpu.tools.tpulint.rules import parse_telemetry_doc
    kinds = parse_telemetry_doc(str(snapshot_root))
    _snapshot(snapshot_root, kinds)
    assert _append_only(snapshot_root) == []


def test_telemetry_append_only_flags_removed_kind_and_field(snapshot_root):
    _snapshot(snapshot_root, {
        "train_step": {"loss", "grad_norm", "overflow"},  # field removed
        "nvme": {"bytes"},                                # kind removed
        "serving": {"tokens_per_s"}})
    found = _append_only(snapshot_root)
    msgs = "\n".join(f.message for f in found)
    assert "kind 'nvme' was removed" in msgs
    assert "field 'overflow' of event 'train_step' was removed" in msgs
    assert all(f.path == "docs/telemetry.md" for f in found)


def test_telemetry_append_only_flags_stale_snapshot(snapshot_root):
    _snapshot(snapshot_root, {"train_step": {"loss", "grad_norm"}})
    found = _append_only(snapshot_root)
    assert len(found) == 1
    assert "snapshot is stale" in found[0].message
    assert "serving" in found[0].message
    assert found[0].path == "docs/telemetry_schema.json"


def test_telemetry_append_only_only_runs_on_anchor(snapshot_root):
    _snapshot(snapshot_root, {"gone_kind": {"x"}})
    assert lint_source("x = 1\n", "deepspeed_tpu/telemetry/metrics.py",
                       root=str(snapshot_root),
                       rules=["telemetry-append-only"]) == []


def test_cli_update_telemetry_snapshot(snapshot_root, capsys, monkeypatch):
    monkeypatch.chdir(snapshot_root)
    assert cli_main(["--update-telemetry-snapshot"]) == 0
    out = capsys.readouterr().out
    assert "2 event kind(s)" in out
    data = json.load(open(snapshot_root / "docs" / "telemetry_schema.json"))
    assert sorted(data["kinds"]) == ["serving", "train_step"]
    assert "loss" in data["kinds"]["train_step"]
    # the snapshot it writes is in sync by construction
    assert _append_only(snapshot_root) == []


# ------------------------------------- rule 8c: telemetry kind declared


def test_telemetry_kind_declared_flags_unsnapshotted_kind(snapshot_root):
    # documented in the doc but NOT re-snapshotted: schema-sync passes,
    # this rule catches the drift
    _snapshot(snapshot_root, {"train_step": {"loss", "grad_norm"}})
    found = lint_source(textwrap.dedent("""
        def report(hub):
            hub.emit("serving", tokens_per_s=1.0)
        """), "deepspeed_tpu/telemetry/hub.py", root=str(snapshot_root),
        rules=["telemetry-kind-declared"])
    assert len(found) == 1
    assert "'serving' is not declared" in found[0].message
    assert "--update-telemetry-snapshot" in found[0].message


def test_telemetry_kind_declared_clean_and_bootstrap(snapshot_root):
    src = 'hub.emit("train_step", loss=1.0)\n'
    # no snapshot on disk → bootstrap, rule stands down
    assert lint_source(src, _ANCHOR, root=str(snapshot_root),
                       rules=["telemetry-kind-declared"]) == []
    _snapshot(snapshot_root, {"train_step": {"loss"}})
    assert lint_source(src, _ANCHOR, root=str(snapshot_root),
                       rules=["telemetry-kind-declared"]) == []
    # tests/ emit synthetic kinds on purpose — out of scope
    assert lint_source('hub.emit("synthetic")\n', "tests/unit/t.py",
                       root=str(snapshot_root),
                       rules=["telemetry-kind-declared"]) == []


# --------------------------------- rule 14: accounted placement routing


def test_accounted_placement_routing_flags_unrouted_host_placement():
    # the ctor is the finding — a device_put fed the sharding via a
    # variable is deliberately NOT double-flagged (one site, one fix)
    found = _lint(
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P(), memory_kind="pinned_host")
        y = jax.device_put(x, sh)
        """, "deepspeed_tpu/inference/kv_cache.py",
        "accounted-placement-routing")
    assert _ids(found) == ["accounted-placement-routing"]
    # an inline host-kind sharding exercises the device_put branch
    found = _lint(
        """
        import jax
        from jax.sharding import SingleDeviceSharding
        z = jax.device_put(
            x, SingleDeviceSharding(dev, memory_kind="unpinned_host"))
        """, "deepspeed_tpu/inference/kv_cache.py",
        "accounted-placement-routing")
    assert len(found) >= 1
    assert "device_put" in found[0].message


def test_accounted_placement_routing_clean_in_accounted_helpers():
    src = """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P(), memory_kind="pinned_host")
        y = jax.device_put(x, sh)
        """
    for path in ("deepspeed_tpu/telemetry/memory.py",
                 "deepspeed_tpu/inference/serve_modes.py",
                 "deepspeed_tpu/inference/capacity_scan.py",
                 "deepspeed_tpu/runtime/swap_tensor/async_swapper.py"):
        assert _lint(src, path, "accounted-placement-routing") == []
    # device-tier placements are never the rule's business
    assert _lint(
        """
        import jax
        y = jax.device_put(x, dev)
        """, "deepspeed_tpu/inference/kv_cache.py",
        "accounted-placement-routing") == []


def test_accounted_placement_routing_pragma_suppresses():
    assert _lint(
        """
        import jax
        # transient staging, gone before the step returns
        sh = NamedSharding(  # tpulint: disable=accounted-placement-routing
            mesh, P(), memory_kind="pinned_host")
        """, "deepspeed_tpu/runtime/engine.py",
        "accounted-placement-routing") == []
