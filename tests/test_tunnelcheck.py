"""tunnelcheck rule suite: positive + negative fixtures per rule, waiver
parsing, and the self-run invariant that the shipped tree stays clean.

Fast and jax-free: the checker is pure ``ast``, so these tests are plain
tier-1 members with no accelerator or optional-dep requirements.
"""

from __future__ import annotations

import textwrap
from pathlib import Path

import pytest

from tools.tunnelcheck import run_paths
from tools.tunnelcheck.__main__ import main as tunnelcheck_main

REPO_ROOT = Path(__file__).resolve().parents[1]


def check(tmp_path: Path, code: str, filename: str = "snippet.py", rules=None):
    """Write one fixture file and return (active, waived) violations."""
    f = tmp_path / filename
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(code))
    return run_paths([f], rules=rules)


def rules_of(violations):
    return [v.rule for v in violations]


# ---------------------------------------------------------------------------
# TC00 — parse errors are findings, not crashes
# ---------------------------------------------------------------------------


def test_tc00_syntax_error_is_reported(tmp_path):
    active, _ = check(tmp_path, "def broken(:\n")
    assert rules_of(active) == ["TC00"]


# ---------------------------------------------------------------------------
# TC01 — blocking calls inside async def
# ---------------------------------------------------------------------------


def test_tc01_flags_time_sleep_in_async(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import time

        async def handler():
            time.sleep(0.1)
        """,
    )
    assert rules_of(active) == ["TC01"]
    assert "asyncio.sleep" in active[0].message


def test_tc01_resolves_from_import_alias(tmp_path):
    active, _ = check(
        tmp_path,
        """
        from time import sleep
        import subprocess as sp

        async def handler():
            sleep(1)
            sp.check_output(["ls"])
        """,
    )
    assert rules_of(active) == ["TC01", "TC01"]


def test_tc01_local_import_does_not_pollute_module_scope(tmp_path):
    # A sync helper's local `from time import sleep` must not make the
    # async function's asyncio `sleep` resolve to time.sleep...
    active, _ = check(
        tmp_path,
        """
        from asyncio import sleep

        def helper():
            from time import sleep
            sleep(1)

        async def handler():
            await sleep(0.1)
        """,
    )
    assert active == []


def test_tc01_local_import_inside_async_def_still_resolves(tmp_path):
    # ...while a local import inside the async def itself still counts.
    active, _ = check(
        tmp_path,
        """
        async def handler():
            from time import sleep
            sleep(1)
        """,
    )
    assert rules_of(active) == ["TC01"]


def test_tc01_rebound_import_resolves_to_last_binding(tmp_path):
    # Python binding semantics: the LAST import of a rebound name wins.
    active, _ = check(
        tmp_path,
        """
        from time import sleep
        from asyncio import sleep

        async def handler():
            await sleep(0.1)
        """,
    )
    assert active == []
    active, _ = check(
        tmp_path,
        """
        from asyncio import sleep
        from time import sleep

        async def handler():
            sleep(0.1)
        """,
    )
    assert rules_of(active) == ["TC01"]


def test_tc01_flags_blocking_file_io(tmp_path):
    active, _ = check(
        tmp_path,
        """
        async def handler(path):
            with open(path) as f:
                return f.read()
        """,
    )
    assert rules_of(active) == ["TC01"]


def test_tc01_allows_sync_and_awaited_equivalents(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import asyncio
        import time

        def sync_helper():
            time.sleep(0.1)  # fine: not on the event loop

        async def handler():
            await asyncio.sleep(0.1)

            def executor_job():
                time.sleep(1)  # fine: nearest enclosing function is sync

            return executor_job
        """,
    )
    assert active == []


# ---------------------------------------------------------------------------
# TC02 — jit signature drift
# ---------------------------------------------------------------------------


def test_tc02_static_argnums_out_of_range(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import jax

        def step(params, tokens, steps):
            return tokens

        fn = jax.jit(step, static_argnums=(2, 7))
        """,
    )
    assert rules_of(active) == ["TC02"]
    assert "index 7" in active[0].message


def test_tc02_static_argnames_unknown_name(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import jax

        def step(params, tokens, steps):
            return tokens

        fn = jax.jit(step, static_argnames=("step_count",))
        """,
    )
    assert rules_of(active) == ["TC02"]
    assert "step_count" in active[0].message


def test_tc02_direct_call_arity(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import jax

        def step(params, tokens, steps):
            return tokens

        out = jax.jit(step, static_argnums=(2,))(p, t)
        """,
    )
    assert rules_of(active) == ["TC02"]
    assert "missing: steps" in active[0].message


def test_tc02_keyword_fun_spelling_is_checked(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import jax

        def step(params, tokens):
            return tokens

        fn = jax.jit(fun=step, static_argnums=(5,))
        """,
    )
    assert rules_of(active) == ["TC02"]


def test_tc02_partial_decorator_checked(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0,))
        def step(params, tokens):
            return tokens
        """,
    )
    assert rules_of(active) == ["TC02"]


def test_tc02_regression_old_perf_probe_shape(tmp_path):
    """The PR 2 incident, verbatim in shape: ``_decode_fn`` grew a ``bias``
    parameter (13 total), but the probe still jitted it with the stale
    ``static_argnums=(10, 11)`` and lowered with the old 12-argument call.
    The indices are in range — only the arity check catches it, exactly the
    class of drift tests never see because scripts/ is never imported."""
    active, _ = check(
        tmp_path,
        """
        import jax

        class Engine:
            def _decode_fn(self, params, kv_cache, tokens, positions, counts,
                           bias, ov_mask, ov_tok, ov_pos, samp, key, kv_view,
                           steps):
                return tokens

        def probe(eng, params, kv_cache, tokens, positions, counts, ovm, ovt,
                  ovp, samp, key, kv_view, steps):
            return jax.jit(eng._decode_fn, static_argnums=(10, 11)).lower(
                params, kv_cache, tokens, positions, counts, ovm, ovt,
                ovp, samp, key, kv_view, steps,
            )
        """,
    )
    assert rules_of(active) == ["TC02"]
    assert "missing" in active[0].message


def test_tc02_clean_on_valid_shapes(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import jax

        class Engine:
            def _decode_fn(self, params, tokens, steps):
                return tokens

        def probe(eng, params, tokens, steps):
            return jax.jit(eng._decode_fn, static_argnums=(2,)).lower(
                params, tokens, steps
            )

        variadic = jax.jit(lambda *a: a, static_argnums=(5,))
        unresolvable = jax.jit(some_imported_fn, static_argnums=(99,))
        """,
    )
    assert active == []


# ---------------------------------------------------------------------------
# TC03 — host sync inside traced functions
# ---------------------------------------------------------------------------


def test_tc03_item_in_jitted_function(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import jax

        def step(carry, x):
            n = carry.item()
            return carry, x

        fn = jax.jit(step)
        """,
    )
    assert rules_of(active) == ["TC03"]
    assert ".item()" in active[0].message


def test_tc03_scan_body_and_np_asarray(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import numpy as np
        from jax import lax

        def body(carry, x):
            host = np.asarray(x)
            return carry, host

        ys = lax.scan(body, 0, xs)
        """,
    )
    assert rules_of(active) == ["TC03"]
    assert "numpy.asarray" in active[0].message


def test_tc03_python_if_on_traced_comparison(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            if jnp.max(x) > 0:
                return x
            return -x
        """,
    )
    assert rules_of(active) == ["TC03"]
    assert "lax.cond" in active[0].message


def test_tc03_float_of_jax_expression(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        def step(x):
            return float(jnp.sum(x))

        fn = jax.jit(step)
        """,
    )
    assert rules_of(active) == ["TC03"]


def test_tc03_static_shape_and_dtype_branches_are_legal(tmp_path):
    # shape/ndim/dtype are plain Python values under trace; branching on
    # them is legal and must not be pushed toward lax.cond.
    active, _ = check(
        tmp_path,
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            if jnp.ndim(x) == 2:
                return x
            if x.shape[0] > 1 and x.dtype == jnp.int8:
                return x
            n = int(jnp.shape(x)[0])
            return -x
        """,
    )
    assert active == []


def test_tc03_traced_parameter_concretisation(tmp_path):
    # float()/if on a traced *parameter* must be caught even with no
    # jnp call in the expression.
    active, _ = check(
        tmp_path,
        """
        import jax

        def step(x, steps):
            if x > 0:
                return float(x)
            return 0.0

        fn = jax.jit(step, static_argnums=(1,))
        """,
    )
    assert rules_of(active) == ["TC03", "TC03"]


def test_tc03_static_argnums_params_are_exempt(tmp_path):
    # Params marked static at the jit site are Python values: branching
    # and float() on them is legal, as is `is None` on traced args.
    active, _ = check(
        tmp_path,
        """
        import jax

        def step(x, mask, steps):
            if steps > 4:
                return x * float(steps)
            if mask is not None:
                return x + mask
            return x

        fn = jax.jit(step, static_argnums=(2,))
        """,
    )
    assert active == []


def test_tc03_scan_carry_name_collision_not_traced(tmp_path):
    # Only the function positions of scan/fori/while are traced; a carry
    # arg sharing its name with a host-side def must not drag it in.
    active, _ = check(
        tmp_path,
        """
        import numpy as np
        from jax import lax

        def helper(x):
            return float(np.asarray(x))

        def body(carry, x):
            return carry, x

        ys = lax.scan(body, helper, xs)
        out = lax.fori_loop(lower, helper, body, init)
        """,
    )
    assert active == []


def test_tc03_untraced_functions_are_free(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import numpy as np

        def host_side(x):
            return float(np.asarray(x).item())

        def static_config(x, use_bias):
            if use_bias:  # static python control flow is fine under trace
                return x
            return -x

        import jax
        fn = jax.jit(static_config, static_argnums=(1,))
        """,
    )
    assert active == []


# ---------------------------------------------------------------------------
# TC04 — optional-dep hygiene
# ---------------------------------------------------------------------------


def test_tc04_module_level_optional_import(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import websockets
        """,
    )
    assert rules_of(active) == ["TC04"]


def test_tc04_gating_try_except_is_still_module_level(tmp_path):
    # Only the three wrapper modules may gate; anyone else must import them.
    active, _ = check(
        tmp_path,
        """
        try:
            from cryptography.hazmat.primitives import hashes
        except ImportError:
            hashes = None
        """,
    )
    assert rules_of(active) == ["TC04"]


def test_tc04_type_checking_block_is_exempt(tmp_path):
    # `if TYPE_CHECKING:` never executes, so a type-only import cannot
    # cause the PR 1 collection-error incident.
    active, _ = check(
        tmp_path,
        """
        from typing import TYPE_CHECKING

        if TYPE_CHECKING:
            import websockets
        """,
    )
    assert active == []


def test_tc04_function_local_import_ok(tmp_path):
    active, _ = check(
        tmp_path,
        """
        def connect():
            import websockets
            return websockets
        """,
    )
    assert active == []


def test_tc04_gated_wrappers_are_exempt(tmp_path):
    active, _ = check(
        tmp_path,
        """
        try:
            import websockets
        except ImportError:
            websockets = None
        """,
        filename="p2p_llm_tunnel_tpu/signaling/client.py",
    )
    assert active == []


# ---------------------------------------------------------------------------
# TC05 — MessageType dispatch exhaustiveness + error-code registry
# ---------------------------------------------------------------------------

DISPATCH_PREAMBLE = """
from p2p_llm_tunnel_tpu.protocol.frames import MessageType, TunnelMessage

def dispatch(msg):
"""


def test_tc05_dispatch_without_default(tmp_path):
    active, _ = check(
        tmp_path,
        DISPATCH_PREAMBLE
        + """
    if msg.msg_type == MessageType.RES_BODY:
        return "body"
    elif msg.msg_type == MessageType.RES_END:
        return "end"
        """,
    )
    assert rules_of(active) == ["TC05"]
    assert "unhandled" in active[0].message


def test_tc05_dispatch_with_default_is_clean(tmp_path):
    active, _ = check(
        tmp_path,
        DISPATCH_PREAMBLE
        + """
    if msg.msg_type == MessageType.RES_BODY:
        return "body"
    elif msg.msg_type == MessageType.RES_END:
        return "end"
    else:
        return "ignored"
        """,
    )
    assert active == []


def test_tc05_else_containing_an_if_is_a_default(tmp_path):
    # An `else:` whose body starts with an `if` must not be mistaken for
    # another elif link — it IS the explicit default.
    active, _ = check(
        tmp_path,
        DISPATCH_PREAMBLE
        + """
    if msg.msg_type == MessageType.RES_BODY:
        return "body"
    elif msg.msg_type == MessageType.RES_END:
        return "end"
    else:
        if msg.stream_id == 0:
            return "control"
        return "ignored"
        """,
    )
    assert active == []


def test_tc05_covers_kv_pages_frame_family(tmp_path):
    """ISSUE 20: a dispatch ladder over the new KV_PAGES_* transfer
    members is a MessageType dispatch like any other — no default arm,
    TC05 fires.  Pins that enum growth grows the rule's coverage for
    free (the exhaustiveness check reads the enum, not a hand list)."""
    active, _ = check(
        tmp_path,
        DISPATCH_PREAMBLE
        + """
    if msg.msg_type == MessageType.KV_PAGES_HDR:
        return "hdr"
    elif msg.msg_type == MessageType.KV_PAGES_CHUNK:
        return "chunk"
    elif msg.msg_type == MessageType.KV_PAGES_END:
        return "end"
        """,
    )
    assert rules_of(active) == ["TC05"]
    assert "unhandled" in active[0].message


def test_tc05_sees_through_import_aliases(tmp_path):
    active, _ = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.protocol.frames import MessageType as MT

        def dispatch(msg):
            if msg.msg_type == MT.RES_BODY:
                return "body"
            elif msg.msg_type == MT.RES_END:
                return "end"
        """,
    )
    assert rules_of(active) == ["TC05"]


def test_tc05_different_subjects_are_not_one_dispatch(tmp_path):
    # Comparing two DIFFERENT expressions against members is not a
    # dispatch over one frame's type.
    active, _ = check(
        tmp_path,
        DISPATCH_PREAMBLE
        + """
    if msg.first.msg_type == MessageType.RES_BODY:
        return "a"
    elif msg.second.msg_type == MessageType.RES_END:
        return "b"
        """,
    )
    assert active == []


def test_tc05_single_guard_is_not_a_dispatch(tmp_path):
    active, _ = check(
        tmp_path,
        DISPATCH_PREAMBLE
        + """
    if msg.msg_type != MessageType.HELLO:
        raise RuntimeError("expected HELLO")
        """,
    )
    assert active == []


def test_tc05_unregistered_typed_error_code(tmp_path):
    active, _ = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.protocol.frames import TunnelMessage

        frame = TunnelMessage.typed_error(1, "overloadedd", "shed")
        """,
    )
    assert rules_of(active) == ["TC05"]
    assert "overloadedd" in active[0].message


def test_tc05_registered_code_and_tunnel_code_clean(tmp_path):
    active, _ = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.protocol.frames import TunnelMessage

        class DeadlineExceeded(Exception):
            tunnel_code = "timeout"

        frame = TunnelMessage.typed_error(1, "busy", "shed")
        """,
    )
    assert active == []


def test_tc05_unregistered_tunnel_code(tmp_path):
    active, _ = check(
        tmp_path,
        """
        class Oops(Exception):
            tunnel_code = "exploded"
        """,
    )
    assert rules_of(active) == ["TC05"]


def test_tc05_annotated_tunnel_code_and_keyword_code(tmp_path):
    # The typed variants must not slip past the registry check.
    active, _ = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.protocol.frames import TunnelMessage

        class Oops(Exception):
            tunnel_code: str = "exploded"

        frame = TunnelMessage.typed_error(1, code="overloadedd", msg="x")
        """,
    )
    assert rules_of(active) == ["TC05", "TC05"]


# ---------------------------------------------------------------------------
# TC06 — metrics-name registry
# ---------------------------------------------------------------------------


def test_tc06_typod_write_is_flagged(tmp_path):
    active, _ = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

        global_metrics.inc("engine_tokens_totl")
        """,
    )
    assert rules_of(active) == ["TC06"]
    assert "engine_tokens_totl" in active[0].message


def test_tc06_typod_read_is_flagged(tmp_path):
    # /healthz-style reads are held to the catalogue too.
    active, _ = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

        depth = global_metrics.gauge("engine_queue_dept")
        """,
    )
    assert rules_of(active) == ["TC06"]


def test_tc06_catalogued_names_are_clean(tmp_path):
    active, _ = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

        global_metrics.inc("engine_tokens_total")
        global_metrics.set_gauge("engine_queue_depth", 3)
        global_metrics.observe("engine_ttft_ms", 12.5)
        depth = global_metrics.gauge("engine_queue_depth")
        dynamic = "engine_" + "tokens_total"
        global_metrics.inc(dynamic)  # non-literal names are out of scope
        """,
    )
    assert active == []


# ---------------------------------------------------------------------------
# Waivers
# ---------------------------------------------------------------------------


def test_line_waiver_suppresses_and_is_reported_as_waived(tmp_path):
    active, waived = check(
        tmp_path,
        """
        import time

        async def handler():
            time.sleep(0.01)  # tunnelcheck: disable=TC01  startup-only path
        """,
    )
    assert active == []
    assert rules_of(waived) == ["TC01"]


def test_line_waiver_is_rule_specific(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import time

        async def handler():
            time.sleep(0.01)  # tunnelcheck: disable=TC02
        """,
    )
    assert rules_of(active) == ["TC01"]


def test_waiver_inside_a_string_literal_is_inert(tmp_path):
    # Only real comment tokens waive — a fixture string that *contains*
    # waiver syntax (like this test file itself) must not gag the checker.
    active, _ = check(
        tmp_path,
        '''
        import time

        FIXTURE = """
        # tunnelcheck: disable-file=TC01
        x = 1  # tunnelcheck: disable=all
        """

        async def handler():
            time.sleep(1)
        ''',
    )
    assert rules_of(active) == ["TC01"]


def test_waiver_on_a_continuation_line_suppresses(tmp_path):
    # The natural placement — next to the offending argument of a
    # multi-line call — must work, not just the statement's first line.
    active, waived = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.utils.metrics import global_metrics

        global_metrics.observe(
            "bench_only_series",  # tunnelcheck: disable=TC06  ad-hoc probe
            1.0,
        )
        """,
    )
    assert active == []
    assert rules_of(waived) == ["TC06"]


def test_file_waiver_and_disable_all(tmp_path):
    active, waived = check(
        tmp_path,
        """
        # tunnelcheck: disable-file=TC01
        import time
        import subprocess

        async def a():
            time.sleep(1)

        async def b():
            subprocess.run(["ls"])  # tunnelcheck: disable=all
        """,
    )
    assert active == []
    assert len(waived) == 2


# ---------------------------------------------------------------------------
# Self-run + CLI
# ---------------------------------------------------------------------------


def test_self_run_shipped_tree_is_clean():
    """The repo must always pass its own checker (the `make lint` gate) —
    including the repo-root entry points chip_smoke.py and
    __graft_entry__.py, which jit the kernels and the model functions."""
    active, _ = run_paths(
        [
            REPO_ROOT / "p2p_llm_tunnel_tpu",
            REPO_ROOT / "scripts",
            REPO_ROOT / "tests",
            REPO_ROOT / "chip_smoke.py",
            REPO_ROOT / "__graft_entry__.py",
        ]
    )
    assert active == [], "\n".join(v.render(REPO_ROOT) for v in active)


def test_overlapping_paths_scan_each_file_once(tmp_path):
    f = tmp_path / "bad.py"
    f.write_text("import time\n\nasync def f():\n    time.sleep(1)\n")
    active, _ = run_paths([tmp_path, f])
    assert rules_of(active) == ["TC01"]


def test_cli_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\n\nasync def f():\n    time.sleep(1)\n")
    good = tmp_path / "good.py"
    good.write_text("x = 1\n")

    assert tunnelcheck_main([str(good)]) == 0
    assert tunnelcheck_main([str(bad)]) == 1
    out = capsys.readouterr().out
    assert "TC01" in out
    assert tunnelcheck_main([]) == 2
    assert tunnelcheck_main([str(tmp_path / "missing.py")]) == 2
    assert tunnelcheck_main(["--list-rules"]) == 0
    assert "TC06" in capsys.readouterr().out


def test_cli_rule_filter(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\n\nasync def f():\n    time.sleep(1)\n")
    assert tunnelcheck_main([str(bad), "--rules", "TC02"]) == 0
    assert tunnelcheck_main([str(bad), "--rules", "TC01"]) == 1
    assert tunnelcheck_main([str(bad), "--rules", "TC99"]) == 2
    # TC00 appears in --list-rules, so the filter accepts it (parse errors
    # are unfilterable and reported regardless of --rules).
    assert tunnelcheck_main([str(bad), "--rules", "TC00"]) == 0
    unparseable = tmp_path / "unparseable.py"
    unparseable.write_text("def broken(:\n")
    assert tunnelcheck_main([str(unparseable), "--rules", "TC06"]) == 1


def test_run_paths_rejects_unknown_rule_ids(tmp_path):
    f = tmp_path / "x.py"
    f.write_text("x = 1\n")
    with pytest.raises(ValueError, match="TC1"):
        run_paths([f], rules=["TC1"])
    # TC00 is accepted (always-on, unfilterable).
    active, _ = run_paths([f], rules=["TC00"])
    assert active == []


def test_registries_match_runtime():
    """The statically-parsed registries agree with the live modules, so the
    checker can't drift from what the code actually enforces."""
    from p2p_llm_tunnel_tpu.protocol.frames import ERROR_CODES, MessageType
    from p2p_llm_tunnel_tpu.utils.metrics import METRICS_CATALOG
    from tools.tunnelcheck.core import ProjectContext

    ctx = ProjectContext([])
    assert set(ctx.message_types) == {m.name for m in MessageType}
    assert ctx.error_codes == set(ERROR_CODES)
    assert ctx.metrics_names == set(METRICS_CATALOG)


# ---------------------------------------------------------------------------
# TC07 — device dispatches inside per-request/slot loops (serving path)
# ---------------------------------------------------------------------------

ENGINE_FIXTURE = "p2p_llm_tunnel_tpu/engine/fixture_engine.py"


def test_tc07_flags_jit_call_in_request_loop(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import jax

        class Engine:
            def __init__(self):
                self._jit_copy = jax.jit(lambda x: x)

            def admit(self, runs):
                for run in runs:
                    self._jit_copy(run)
        """,
        filename=ENGINE_FIXTURE,
        rules=["TC07"],
    )
    assert rules_of(active) == ["TC07"]
    assert "_jit_copy" in active[0].message


def test_tc07_flags_device_get_in_request_loop(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import jax

        def drain(requests):
            out = []
            for r in requests:
                out.append(jax.device_get(r))
            return out
        """,
        filename=ENGINE_FIXTURE,
        rules=["TC07"],
    )
    assert rules_of(active) == ["TC07"]


def test_tc07_flags_factory_returned_callable_per_slot(tmp_path):
    """The exact r5 class: a helper factory returns jitted copy ops
    (tuple-unpacked), and one of them is dispatched once per matched
    request inside the admission loop."""
    active, _ = check(
        tmp_path,
        """
        import jax

        def make_copy_ops():
            return jax.jit(lambda c: c), jax.jit(lambda c: c)

        class Engine:
            def __init__(self):
                self._copy_in, self._copy_out = make_copy_ops()

            def admit(self, hits):
                for slot, blocks in hits:
                    self.cache = self._copy_in(self.cache)
        """,
        filename=ENGINE_FIXTURE,
        rules=["TC07"],
    )
    assert rules_of(active) == ["TC07"]
    assert "_copy_in" in active[0].message


@pytest.mark.parametrize("handoff", [
    "loop.run_in_executor(None, self._dispatch_one, run)",
    # the engine loop's timed run_in_executor (ISSUE 57): same place
    "self._offload(loop, self._dispatch_one, run)",
])
def test_tc07_flags_dispatching_helper_via_executor(tmp_path, handoff):
    """A method that transitively dispatches, handed to run_in_executor
    once per request, is still one dispatch per iteration."""
    active, _ = check(
        tmp_path,
        """
        import jax

        class Engine:
            def __init__(self):
                self._jit_prefill = jax.jit(lambda t: t)

            def _dispatch_one(self, tokens):
                return self._jit_prefill(tokens)

            async def admit(self, loop, admitted):
                for run in admitted:
                    await HANDOFF
        """.replace("HANDOFF", handoff),
        filename=ENGINE_FIXTURE,
        rules=["TC07"],
    )
    assert rules_of(active) == ["TC07"]


def test_tc07_batched_outside_loop_and_warmup_loops_clean(tmp_path):
    """The fixed shape (pack the wave, ONE dispatch after the loop) and
    compile-time loops over view buckets are clean; so is the engine's
    `while self._running` main loop (word-wise subject matching — one
    dispatch per BURST is the design)."""
    active, _ = check(
        tmp_path,
        """
        import jax

        class Engine:
            def __init__(self):
                self._jit_prefill = jax.jit(lambda t: t)
                self._running = True

            def admit(self, runs):
                batch = [r.tokens for r in runs]
                return self._jit_prefill(batch)

            def warmup(self, views):
                for view in views:
                    self._jit_prefill(view)

            def loop(self):
                while self._running:
                    self._jit_prefill(0)
        """,
        filename=ENGINE_FIXTURE,
        rules=["TC07"],
    )
    assert active == []


def test_tc07_out_of_scope_modules_not_scanned(tmp_path):
    """The rule covers the engine/endpoints serving path only — model
    code legitimately maps jitted fns over layer lists."""
    active, _ = check(
        tmp_path,
        """
        import jax

        def apply(layers):
            f = jax.jit(lambda x: x)
            for layer in layers:  # 'layer' is not a request subject anyway
                f(layer)

        def per_prompt(prompts):
            g = jax.jit(lambda x: x)
            for p in prompts:
                g(p)
        """,
        filename="p2p_llm_tunnel_tpu/models/fixture_model.py",
        rules=["TC07"],
    )
    assert active == []


def test_tc07_waiver_records_granularity_contract(tmp_path):
    active, waived = check(
        tmp_path,
        """
        import jax

        class Engine:
            def __init__(self):
                self._jit_copy = jax.jit(lambda x: x)

            def admit(self, hits):
                for lo in range(0, len(hits), 8):
                    self._jit_copy(hits[lo:lo + 8])  # tunnelcheck: disable=TC07  one dispatch per 8-wide sub-batch
        """,
        filename=ENGINE_FIXTURE,
        rules=["TC07"],
    )
    assert active == []
    assert rules_of(waived) == ["TC07"]


# ---------------------------------------------------------------------------
# TC08 — EngineConfig fields must be wired to cli.py flags (config rot)
# ---------------------------------------------------------------------------


def _tc08_tree(tmp_path, engine_src, cli_src):
    (tmp_path / "pkg").mkdir(exist_ok=True)
    eng = tmp_path / "pkg" / "engine.py"
    eng.write_text(textwrap.dedent(engine_src))
    cli = tmp_path / "pkg" / "cli.py"
    cli.write_text(textwrap.dedent(cli_src))
    return run_paths([eng, cli], rules=["TC08"])


def test_tc08_unwired_field_is_flagged(tmp_path):
    active, _ = _tc08_tree(
        tmp_path,
        """
        from dataclasses import dataclass

        @dataclass
        class EngineConfig:
            model: str = "tiny"
            zz_orphan_knob: int = 0
        """,
        """
        from pkg.engine import EngineConfig

        def make(args):
            return EngineConfig(model=args.model)
        """,
    )
    assert rules_of(active) == ["TC08"]
    assert "zz_orphan_knob" in active[0].message


def test_tc08_regression_env_only_serving_levers(tmp_path):
    """The incident class this rule exists for: decode_steps_eager and
    prefill_rows were REAL serving levers (benched via BENCH_* env knobs,
    documented in README) that no serve flag could reach for four PRs —
    operators of the deployed binary simply could not turn the TTFT lever.
    The fixture mirrors that exact shape."""
    active, _ = _tc08_tree(
        tmp_path,
        """
        from dataclasses import dataclass

        @dataclass
        class EngineConfig:
            model: str = "tiny"
            decode_steps: int = 8
            decode_steps_eager: int = 4
            prefill_rows: int = 8
        """,
        """
        from pkg.engine import EngineConfig

        def make(args):
            return EngineConfig(
                model=args.model, decode_steps=args.decode_steps,
            )
        """,
    )
    assert sorted(v.message.split()[0] for v in active) == [
        "EngineConfig.decode_steps_eager",
        "EngineConfig.prefill_rows",
    ]


def test_tc08_wired_fields_are_clean(tmp_path):
    active, _ = _tc08_tree(
        tmp_path,
        """
        from dataclasses import dataclass

        @dataclass
        class EngineConfig:
            model: str = "tiny"
            slots: int = 8
        """,
        """
        from pkg.engine import EngineConfig

        def make(args):
            return EngineConfig(model=args.model, slots=args.slots)
        """,
    )
    assert active == []


def test_tc08_waiver_names_the_reason(tmp_path):
    active, waived = _tc08_tree(
        tmp_path,
        """
        from dataclasses import dataclass

        @dataclass
        class EngineConfig:
            model: str = "tiny"
            bucket: int = 16  # tunnelcheck: disable=TC08  geometry pin, programmatic only
        """,
        """
        from pkg.engine import EngineConfig

        def make(args):
            return EngineConfig(model=args.model)
        """,
    )
    assert active == []
    assert rules_of(waived) == ["TC08"]


def test_tc08_fixture_without_cli_checks_against_repo_cli(tmp_path):
    """Scanning an EngineConfig definition WITHOUT a cli.py in the scan
    set falls back to the repo's real CLI — so `tunnelcheck engine.py`
    alone still catches rot, and a bogus field is flagged against it."""
    f = tmp_path / "engine.py"
    f.write_text(textwrap.dedent(
        """
        from dataclasses import dataclass

        @dataclass
        class EngineConfig:
            model: str = "tiny"
            zz_never_a_real_flag: int = 0
        """
    ))
    active, _ = run_paths([f], rules=["TC08"])
    assert rules_of(active) == ["TC08"]
    assert "zz_never_a_real_flag" in active[0].message


# ---------------------------------------------------------------------------
# TC09 — span-name registry + host-only emission (ISSUE 6)
# ---------------------------------------------------------------------------


def test_tc09_unknown_span_name_is_flagged(tmp_path):
    active, _ = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

        def emit(tid):
            global_tracer.add_span("engine.queue_wiat", trace_id=tid, t0=0.0)
        """,
        rules=["TC09"],
    )
    assert rules_of(active) == ["TC09"]
    assert "SPAN_CATALOG" in active[0].message


def test_tc09_catalogued_names_and_dynamic_names_are_clean(tmp_path):
    active, _ = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

        def emit(tid, name):
            global_tracer.add_span("engine.request", trace_id=tid, t0=0.0)
            global_tracer.add_event("engine.first_token", trace_id=tid)
            global_tracer.add_event(name, trace_id=tid)  # non-literal: skipped
        """,
        rules=["TC09"],
    )
    assert active == []


def test_tc09_emission_inside_jitted_function_is_flagged(tmp_path):
    """Span emission is host-only: a recorder call inside a function this
    module jits (or scans) is a tracer error at best, a per-step host sync
    at worst — flagged even when the span name itself is legal."""
    active, _ = check(
        tmp_path,
        """
        import jax
        from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

        def step(x):
            global_tracer.add_event("engine.first_token", trace_id="ab")
            return x + 1

        fast = jax.jit(step)
        """,
        rules=["TC09"],
    )
    assert rules_of(active) == ["TC09"]
    assert "host-only" in active[0].message


def test_tc09_emission_inside_scanned_function_is_flagged(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import jax
        from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

        def body(carry, x):
            global_tracer.add_span("engine.decode_burst", trace_id=None,
                                   t0=0.0)
            return carry, x

        def run(xs):
            return jax.lax.scan(body, 0, xs)
        """,
        rules=["TC09"],
    )
    assert rules_of(active) == ["TC09"]


def test_tc09_waiver_suppresses(tmp_path):
    active, waived = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

        def emit(tid):
            global_tracer.add_event(
                "adhoc.probe", trace_id=tid,
            )  # tunnelcheck: disable=TC09  one-off debugging probe
        """,
        rules=["TC09"],
    )
    assert active == []
    assert rules_of(waived) == ["TC09"]


def test_tc09_emit_sites_match_the_shipped_catalog():
    """The repo's own emit sites (proxy, serve, engine) stay aligned with
    SPAN_CATALOG — the narrow self-run gate for TC09."""
    active, _ = run_paths(
        [
            REPO_ROOT / "p2p_llm_tunnel_tpu" / "endpoints",
            REPO_ROOT / "p2p_llm_tunnel_tpu" / "engine",
            REPO_ROOT / "p2p_llm_tunnel_tpu" / "utils",
        ],
        rules=["TC09"],
    )
    assert active == [], [v.render(REPO_ROOT) for v in active]


def test_tc08_self_run_every_field_wired_or_waived():
    """The shipped EngineConfig stays rot-free: every field has a serve
    flag or carries a reasoned waiver (the self-run gate for TC08,
    narrower and faster than the full-tree self-run above)."""
    active, waived = run_paths(
        [
            REPO_ROOT / "p2p_llm_tunnel_tpu" / "engine" / "engine.py",
            REPO_ROOT / "p2p_llm_tunnel_tpu" / "cli.py",
        ],
        rules=["TC08"],
    )
    assert active == [], [v.render(REPO_ROOT) for v in active]
    # The deliberate env/programmatic-only fields stay visible as waivers,
    # not silently absent.
    waived_fields = {v.message.split()[0] for v in waived}
    assert "EngineConfig.min_prefill_bucket" in waived_fields
    assert "EngineConfig.prefix_tail_buckets" in waived_fields


# ---------------------------------------------------------------------------
# TC10 — every queue/buffer on the frame-mux path declares its bound (ISSUE 7)
# ---------------------------------------------------------------------------


def test_tc10_unbounded_queue_and_deque_flagged_in_scope(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import asyncio
        from collections import deque

        events = asyncio.Queue()
        backlog = deque()
        """,
        filename="endpoints/snippet.py",
        rules=["TC10"],
    )
    assert rules_of(active) == ["TC10", "TC10"]
    assert "backpressure" in active[0].message


def test_tc10_explicitly_unbounded_still_flags(tmp_path):
    """Literal maxsize=0 / maxlen=None assert unboundedness without naming
    the compensating mechanism — say it in a waiver instead."""
    active, _ = check(
        tmp_path,
        """
        import asyncio
        import collections

        q = asyncio.Queue(maxsize=0)
        d = collections.deque(maxlen=None)
        """,
        filename="transport/snippet.py",
        rules=["TC10"],
    )
    assert rules_of(active) == ["TC10", "TC10"]
    assert "explicitly unbounded" in active[0].message


def test_tc10_bounded_constructions_are_clean(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import asyncio
        from collections import deque

        CAP = 64
        q1 = asyncio.Queue(maxsize=256)
        q2 = asyncio.Queue(CAP)
        d1 = deque(maxlen=8)
        d2 = deque([], 8)
        """,
        filename="protocol/snippet.py",
        rules=["TC10"],
    )
    assert active == []


def test_tc10_out_of_scope_dirs_are_exempt(tmp_path):
    """engine/ (and anything else off the frame-mux path) is out of scope:
    its per-request queues are bounded by max_new_tokens per stream and
    audited by the serving-path rules."""
    active, _ = check(
        tmp_path,
        """
        import asyncio

        q = asyncio.Queue()
        """,
        filename="engine/snippet.py",
        rules=["TC10"],
    )
    assert active == []


def test_tc10_waiver_names_the_backpressure_provider(tmp_path):
    active, waived = check(
        tmp_path,
        """
        import asyncio

        q = asyncio.Queue()  # tunnelcheck: disable=TC10  bounded in bytes by FLOW credit
        """,
        filename="endpoints/snippet.py",
        rules=["TC10"],
    )
    assert active == []
    assert rules_of(waived) == ["TC10"]


# ---------------------------------------------------------------------------
# TC11 — retry/backoff loops bounded + jittered (ISSUE 8)
# ---------------------------------------------------------------------------


def test_tc11_uncapped_unjittered_retry_loop_flags_both(tmp_path):
    """The reference's bare exponential: grows without bound AND re-dials
    a whole fleet in lockstep — one violation for each missing property."""
    active, _ = check(
        tmp_path,
        """
        import asyncio

        async def reconnect(attempt_fn):
            attempt = 0
            while True:
                attempt += 1
                try:
                    await attempt_fn()
                    return
                except Exception:
                    pass
                backoff = 2.0 * (2 ** (attempt - 1))
                await asyncio.sleep(backoff)
        """,
        filename="transport/snippet.py",
        rules=["TC11"],
    )
    assert rules_of(active) == ["TC11", "TC11"]
    assert "without a bound" in active[0].message
    assert "jitter" in active[1].message


def test_tc11_self_doubling_augassign_is_growth(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import asyncio
        import random

        async def redial():
            backoff = 0.1
            while True:
                backoff *= 2
                backoff *= 1.0 + random.uniform(0.0, 0.25)
                await asyncio.sleep(backoff)
        """,
        filename="endpoints/snippet.py",
        rules=["TC11"],
    )
    # Jittered, but `backoff *= 2` has no cap.
    assert rules_of(active) == ["TC11"]
    assert "without a bound" in active[0].message


def test_tc11_capped_jittered_loop_is_clean(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import asyncio
        import random

        async def reconnect(attempt_fn):
            attempt = 0
            while True:
                attempt += 1
                try:
                    await attempt_fn()
                    return
                except Exception:
                    pass
                backoff = min(2.0 * (2 ** (attempt - 1)), 60.0)
                backoff *= 1.0 + random.uniform(0.0, 0.25)
                await asyncio.sleep(backoff)
        """,
        filename="snippet/cli.py",
        rules=["TC11"],
    )
    assert active == []


def test_tc11_bounded_for_range_counts_as_the_attempt_bound(tmp_path):
    """`for attempt in range(N)` bounds attempts even when the backoff
    expression itself is a bare exponential — but jitter is still required
    (and present here via the wait_for timeout spelling)."""
    active, _ = check(
        tmp_path,
        """
        import asyncio
        import random

        async def dial(stop):
            for attempt in range(1, 4):
                backoff = 1.0 * (2 ** (attempt - 1))
                backoff *= 1.0 + random.uniform(0.0, 0.5)
                try:
                    await asyncio.wait_for(stop.wait(), backoff)
                except asyncio.TimeoutError:
                    pass
        """,
        filename="transport/snippet.py",
        rules=["TC11"],
    )
    assert active == []


def test_tc11_fixed_interval_loops_are_out_of_scope(tmp_path):
    """Keepalives and probers sleep a CONSTANT interval — no growth, no
    retry semantics, no finding."""
    active, _ = check(
        tmp_path,
        """
        import asyncio

        PING_INTERVAL = 10.0

        async def keepalive(ch):
            while True:
                await asyncio.sleep(PING_INTERVAL)
                await ch.ping()
        """,
        filename="endpoints/snippet.py",
        rules=["TC11"],
    )
    assert active == []


def test_tc11_sleep_in_nested_def_does_not_attribute_to_outer_loop(tmp_path):
    """A callback defined inside a loop runs when called, not per
    iteration — its sleep belongs to no enclosing retry loop."""
    active, _ = check(
        tmp_path,
        """
        import asyncio

        async def outer(items):
            while True:
                n = 2 ** 3

                async def cb():
                    await asyncio.sleep(0.1)

                await register(cb)
        """,
        filename="transport/snippet.py",
        rules=["TC11"],
    )
    assert active == []


def test_tc11_out_of_scope_dirs_are_exempt(tmp_path):
    active, _ = check(
        tmp_path,
        """
        import asyncio

        async def poll(attempt):
            while True:
                attempt += 1
                backoff = 2 ** attempt
                await asyncio.sleep(backoff)
        """,
        filename="engine/snippet.py",
        rules=["TC11"],
    )
    assert active == []


def test_tc11_waiver_names_the_bound(tmp_path):
    active, waived = check(
        tmp_path,
        """
        import asyncio

        async def rto_loop(tries):
            while True:
                tries += 1
                rto = 0.2 * (2 ** min(tries, 4))
                await asyncio.sleep(rto)  # tunnelcheck: disable=TC11  exponent clamped at 2^4, jitter-free: pacing follows the measured RTT
        """,
        filename="transport/snippet.py",
        rules=["TC11"],
    )
    assert active == []
    assert rules_of(waived) == ["TC11", "TC11"]


def test_tc11_repo_retry_loops_are_detected_not_just_absent():
    """Meta-fixture: strip the jitter multiply out of the REAL
    cli.run_with_retry source and TC11 must fire — proving the shipped
    loop passes because it satisfies the rule, not because the detector
    misses it."""
    import re

    src = (REPO_ROOT / "p2p_llm_tunnel_tpu" / "cli.py").read_text()
    stripped = re.sub(
        r"backoff \*= 1\.0 \+ random\.uniform\(0\.0, 0\.25\)", "pass", src
    )
    assert stripped != src
    active, _ = check_path_text(stripped)
    assert any(
        v.rule == "TC11" and "jitter" in v.message for v in active
    ), "de-jittered run_with_retry must trip TC11"


def check_path_text(text: str):
    """Run only TC11 over literal file text named cli.py (scope by name)."""
    import tempfile
    from pathlib import Path as _P

    with tempfile.TemporaryDirectory() as d:
        f = _P(d) / "cli.py"
        f.write_text(text)
        return run_paths([f], rules=["TC11"])


# ---------------------------------------------------------------------------
# TC12 — labeled Prometheus series only through the bounded registry
# ---------------------------------------------------------------------------


def test_tc12_flags_fstring_label_interpolation(tmp_path):
    active, _ = check(
        tmp_path,
        '''
        def render(tenant, v):
            return f'tenant_tokens_total{{tenant="{tenant}"}} {v}'
        ''',
        rules=["TC12"],
    )
    assert rules_of(active) == ["TC12"]
    assert "set_labeled_gauge" in active[0].message


def test_tc12_flags_percent_and_format_interpolation(tmp_path):
    active, _ = check(
        tmp_path,
        '''
        def render(pid, v):
            a = 'x{peer="%s"} %g' % (pid, v)
            b = 'x{peer="{}"} {}'.format(pid, v)
            return a, b
        ''',
        rules=["TC12"],
    )
    assert rules_of(active) == ["TC12", "TC12"]


def test_tc12_ignores_plain_literals_and_unrelated_fstrings(tmp_path):
    # Non-interpolated label literals (test assertions against exposition
    # output) carry no cardinality risk; f-strings without label syntax
    # in their CONSTANT parts are someone else's business.
    active, _ = check(
        tmp_path,
        '''
        def asserts(text, q):
            assert 'tenant_in_flight{tenant="a"} 1' in text
            assert f'quantile="{q}"' in text
            return f"plain {q} interpolation"
        ''',
        rules=["TC12"],
    )
    assert active == []


def test_tc12_waiver_and_registry_exemption(tmp_path):
    active, waived = check(
        tmp_path,
        '''
        def render(t):
            return f'x{{tenant="{t}"}} 1'  # tunnelcheck: disable=TC12  fixture
        ''',
        rules=["TC12"],
    )
    assert active == [] and rules_of(waived) == ["TC12"]
    # The registry module itself is the ONE legal interpolation site.
    active, _ = check(
        tmp_path,
        '''
        def prom_sample(name, k, v, val):
            return f'{name}{{{k}="{v}"}} {val}'
        ''',
        filename="p2p_llm_tunnel_tpu/utils/metrics.py",
        rules=["TC12"],
    )
    assert active == []


def test_tc12_bounded_helper_is_actually_bounded():
    """The helpers TC12 points at must honor their cap: past LABELED_CAP
    distinct labels the least-recently-set is evicted, so the rule's
    cardinality story is enforced at runtime too."""
    from p2p_llm_tunnel_tpu.utils.metrics import LABELED_CAP, Metrics

    m = Metrics()
    for i in range(LABELED_CAP + 10):
        m.set_labeled_gauge("fleet_peer_scrape_stale", "peer",
                            f"p{i:04d}", float(i))
    got = m.labeled_gauge("fleet_peer_scrape_stale")
    assert len(got) == LABELED_CAP
    assert "p0000" not in got and f"p{LABELED_CAP + 9:04d}" in got


# ---------------------------------------------------------------------------
# TC13 — await-atomicity: shared RMW across a suspension point
# ---------------------------------------------------------------------------

PEERS_FIXTURE = "p2p_llm_tunnel_tpu/endpoints/fixture_peers.py"


def test_tc13_stale_local_rmw_across_await(tmp_path):
    active, _ = check(
        tmp_path,
        """
        class Breaker:
            def ok(self):
                return self.failures < 3

            async def probe(self, peer):
                n = self.failures
                await peer.send(b"probe")
                self.failures = n + 1
        """,
        filename=PEERS_FIXTURE,
        rules=["TC13"],
    )
    assert rules_of(active) == ["TC13"]
    assert "stale local `n`" in active[0].message
    assert "failures" in active[0].message


def test_tc13_check_then_act_across_await(tmp_path):
    active, _ = check(
        tmp_path,
        """
        class Breaker:
            def ok(self):
                return self.failures < 3

            async def probe(self, peer):
                if self.failures >= 3:
                    await peer.send(b"probe")
                    self.failures = 0
        """,
        filename=PEERS_FIXTURE,
        rules=["TC13"],
    )
    assert rules_of(active) == ["TC13"]


def test_tc13_reread_after_await_is_clean(tmp_path):
    """The check-again idiom: a fresh read after the suspension refreshes
    the premise, so the write is NOT torn."""
    active, _ = check(
        tmp_path,
        """
        class Breaker:
            def ok(self):
                return self.failures < 3

            async def probe(self, peer):
                await peer.send(b"probe")
                self.failures = self.failures + 1
        """,
        filename=PEERS_FIXTURE,
        rules=["TC13"],
    )
    assert active == []


def test_tc13_lock_held_rmw_is_clean(tmp_path):
    active, _ = check(
        tmp_path,
        """
        class Breaker:
            def ok(self):
                return self.failures < 3

            async def probe(self, peer):
                async with self._lock:
                    n = self.failures
                    await peer.send(b"probe")
                    self.failures = n + 1
        """,
        filename=PEERS_FIXTURE,
        rules=["TC13"],
    )
    assert active == []


def test_tc13_single_accessor_attr_is_exempt(tmp_path):
    """An attribute only ONE function ever touches has a single-writer
    contract by construction — no second accessor can interleave."""
    active, _ = check(
        tmp_path,
        """
        class Loop:
            async def run(self, peer):
                n = self._only_here
                await peer.send(b"x")
                self._only_here = n + 1
        """,
        filename=PEERS_FIXTURE,
        rules=["TC13"],
    )
    assert active == []


def test_tc13_blind_write_after_await_is_clean(tmp_path):
    """A write whose value does not depend on a pre-await read (keepalive
    timestamp stamping) is not a read-modify-write."""
    active, _ = check(
        tmp_path,
        """
        import time

        class Keepalive:
            def read(self):
                return self._sent_at

            async def run(self, peer):
                while True:
                    await peer.sleep(1)
                    self._sent_at = time.monotonic()
                    await peer.send(b"ping")
        """,
        filename=PEERS_FIXTURE,
        rules=["TC13"],
    )
    assert active == []


def test_tc13_waiver_names_the_owning_task(tmp_path):
    active, waived = check(
        tmp_path,
        """
        class Loop:
            def read(self):
                return self._progress

            async def run(self, peer):
                n = self._progress
                await peer.send(b"x")
                self._progress = n + 1  # tunnelcheck: disable=TC13  single-writer: the engine loop task owns decode progress
        """,
        filename=PEERS_FIXTURE,
        rules=["TC13"],
    )
    assert active == []
    assert rules_of(waived) == ["TC13"]


def test_tc13_meta_breaker_half_open_wedge(tmp_path):
    """The rule reproduces its incident (the TC02/TC11 pattern): the PR 8
    review breaker bug — half-open bookkeeping decided from a
    consec_failures read taken BEFORE the probe dispatch's await, so a
    concurrent failure in the await window was silently erased."""
    active, _ = check(
        tmp_path,
        """
        CB_THRESHOLD = 3

        class PeerSet:
            def dispatchable(self, link):
                return link.consec_failures < CB_THRESHOLD

            async def half_open_probe(self, link, msg):
                tripped = link.consec_failures >= CB_THRESHOLD
                await link.channel.send(msg)
                if tripped:
                    link.consec_failures = 0
        """,
        filename=PEERS_FIXTURE,
        rules=["TC13"],
    )
    assert rules_of(active) == ["TC13"]
    assert "consec_failures" in active[0].message
    assert "interleave" in active[0].message


# ---------------------------------------------------------------------------
# TC14 — header taint must pass a registered sanitizer before trusted sinks
# ---------------------------------------------------------------------------

API_FIXTURE = "p2p_llm_tunnel_tpu/endpoints/fixture_api.py"


def test_tc14_meta_pre_pr7_tenant_minting(tmp_path):
    """The rule reproduces its incident: the pre-PR-7 ingress took the raw
    x-tunnel-tenant header bytes as the scheduler identity AND the metric
    label — the exact minting hole parse_tenant closed."""
    active, _ = check(
        tmp_path,
        """
        async def handle(req, payload, global_metrics):
            tenant = ""
            for k, v in req.headers.items():
                if k.lower() == "x-tunnel-tenant":
                    tenant = v
            kwargs = {}
            if tenant:
                kwargs["tenant"] = tenant
                global_metrics.tenant_begin(tenant)
            return kwargs
        """,
        filename=API_FIXTURE,
        rules=["TC14"],
    )
    assert rules_of(active) == ["TC14", "TC14"]
    assert any("scheduler tenant identity" in v.message for v in active)
    assert any("per-tenant accounting" in v.message for v in active)


def test_tc14_sanitized_ingress_is_clean(tmp_path):
    active, _ = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.protocol.frames import parse_tenant

        async def handle(req, global_metrics):
            tenant = parse_tenant(req.headers)
            kwargs = {}
            if tenant:
                kwargs["tenant"] = tenant
                global_metrics.tenant_begin(tenant)
            return kwargs
        """,
        filename=API_FIXTURE,
        rules=["TC14"],
    )
    assert active == []


def test_tc14_headers_param_seeds_taint(tmp_path):
    active, _ = check(
        tmp_path,
        """
        def account(headers, global_metrics):
            for k, v in headers.items():
                if k == "x-tunnel-tenant":
                    global_metrics.tenant_tokens(v)
        """,
        filename=API_FIXTURE,
        rules=["TC14"],
    )
    assert rules_of(active) == ["TC14"]


def test_tc14_labeled_gauge_and_log_interpolation_sinks(tmp_path):
    active, _ = check(
        tmp_path,
        """
        def publish(req, metrics, log):
            raw = req.headers.get("x-tunnel-tenant", "")
            metrics.set_labeled_gauge("tenant_inflight", "tenant", raw, 1.0)
            log.warning(f"tenant {raw} over limit")
            log.error("tenant {t} over limit".format(t=raw))
            log.warning("tenant %s over limit", raw)  # lazy args: exempt
        """,
        filename=API_FIXTURE,
        rules=["TC14"],
    )
    assert rules_of(active) == ["TC14", "TC14", "TC14"]
    assert any("labeled-metrics" in v.message for v in active)
    assert any("log interpolation" in v.message for v in active)


def test_tc14_relay_target_sink(tmp_path):
    active, _ = check(
        tmp_path,
        """
        async def relay(req, signaling):
            target = req.headers.get("x-relay-to", "")
            await signaling.send({"type": "relay", "to": target})
        """,
        filename=API_FIXTURE,
        rules=["TC14"],
    )
    assert rules_of(active) == ["TC14"]
    assert "relay" in active[0].message


def test_tc14_numeric_coercion_sanitizes(tmp_path):
    active, _ = check(
        tmp_path,
        """
        def weight(headers, scheduler):
            w = int(headers.get("x-weight", "1"))
            scheduler.charge_tokens(w, 1)
        """,
        filename=API_FIXTURE,
        rules=["TC14"],
    )
    assert active == []


def test_tc14_waiver(tmp_path):
    active, waived = check(
        tmp_path,
        """
        def account(headers, global_metrics):
            v = headers.get("x-tunnel-tenant", "")
            global_metrics.tenant_begin(v)  # tunnelcheck: disable=TC14  fixture: proxy-stamped header, trusted inside the tunnel
        """,
        filename=API_FIXTURE,
        rules=["TC14"],
    )
    assert active == []
    assert rules_of(waived) == ["TC14"]


def test_tc14_out_of_scope_tree_is_free(tmp_path):
    active, _ = check(
        tmp_path,
        """
        def account(headers, global_metrics):
            global_metrics.tenant_begin(headers.get("t", ""))
        """,
        filename="somewhere_else.py",
        rules=["TC14"],
    )
    assert active == []


# ---------------------------------------------------------------------------
# TC15 — resource lifecycle: release on every exit path, aclose() included
# ---------------------------------------------------------------------------

ENG_FIXTURE = "p2p_llm_tunnel_tpu/engine/fixture_lifecycle.py"


def test_tc15_meta_pre_pr6_finish_after_final_yield(tmp_path):
    """The rule reproduces its incident: pre-PR-6 generate() emitted the
    request span AFTER the yield loop — a consumer that stops iterating
    closes the generator at the yield (GeneratorExit) and the emission
    never runs, logging every normal finish as a leaked/cancelled span."""
    active, _ = check(
        tmp_path,
        """
        async def generate(self, req, queue, global_tracer):
            span = new_span_id()
            while True:
                event = await queue.get()
                if event is None:
                    break
                yield event
            global_tracer.add_span(
                "engine.request", trace_id=req.trace, span_id=span,
            )
        """,
        filename=ENG_FIXTURE,
        rules=["TC15"],
    )
    assert rules_of(active) == ["TC15"]
    assert "aclose" in active[0].message
    assert "span" in active[0].message


def test_tc15_finally_release_is_clean(tmp_path):
    active, _ = check(
        tmp_path,
        """
        async def generate(self, req, queue, global_tracer):
            span = new_span_id()
            try:
                while True:
                    event = await queue.get()
                    if event is None:
                        return
                    yield event
            finally:
                global_tracer.add_span(
                    "engine.request", trace_id=req.trace, span_id=span,
                )
        """,
        filename=ENG_FIXTURE,
        rules=["TC15"],
    )
    assert active == []


def test_tc15_inflight_registry_across_await(tmp_path):
    active, _ = check(
        tmp_path,
        """
        async def fetch(self, link, sid, q):
            link.pending[sid] = q
            await link.channel.send(b"x")
            link.pending.pop(sid, None)
        """,
        filename=ENG_FIXTURE,
        rules=["TC15"],
    )
    assert rules_of(active) == ["TC15"]
    assert "link.pending" in active[0].message


def test_tc15_inflight_registry_finally_is_clean(tmp_path):
    active, _ = check(
        tmp_path,
        """
        async def fetch(self, link, sid, q):
            link.pending[sid] = q
            try:
                await link.channel.send(b"x")
            finally:
                link.pending.pop(sid, None)
        """,
        filename=ENG_FIXTURE,
        rules=["TC15"],
    )
    assert active == []


def test_tc15_straight_line_release_is_clean(tmp_path):
    active, _ = check(
        tmp_path,
        """
        def requeue(self, sid, q):
            self.pending[sid] = q
            self.counts[sid] = self.counts.get(sid, 0) + 1
            self.pending.pop(sid)
        """,
        filename=ENG_FIXTURE,
        rules=["TC15"],
    )
    assert active == []


def test_tc15_local_buffer_is_not_a_registry(tmp_path):
    """A bare-name dict local to the frame (pending_lp accumulation) dies
    with the frame — only parameters count as passed-in shared registries."""
    active, _ = check(
        tmp_path,
        """
        async def stream(self, queue):
            pending_lp = {}
            while True:
                i = await queue.get()
                if i is None:
                    break
                pending_lp[i] = i
                yield i
        """,
        filename=ENG_FIXTURE,
        rules=["TC15"],
    )
    assert active == []


def test_tc15_param_registry_counts(tmp_path):
    active, _ = check(
        tmp_path,
        """
        def plan(wave, inflight):
            for rid in wave:
                inflight[rid] = rid
            return wave
        """,
        filename=ENG_FIXTURE,
        rules=["TC15"],
    )
    assert rules_of(active) == ["TC15"]


def test_tc15_delegated_closure_release_satisfies(tmp_path):
    """A nested closure owning the release (drop_stream/finish_span) is
    the delegated-owner contract the proxy dispatch path uses."""
    active, _ = check(
        tmp_path,
        """
        async def dispatch(self, link, sid, q):
            link.pending[sid] = q

            def drop_stream():
                link.pending.pop(sid, None)

            try:
                await link.channel.send(b"x")
            except Exception:
                drop_stream()
                raise
            return drop_stream
        """,
        filename=ENG_FIXTURE,
        rules=["TC15"],
    )
    assert active == []


def test_tc15_crypto_box_open_is_not_an_acquire(tmp_path):
    active, _ = check(
        tmp_path,
        """
        async def decrypt(self, data):
            plain = self._box.open(data)
            await self.deliver(plain)
        """,
        filename="p2p_llm_tunnel_tpu/transport/fixture_crypto.py",
        rules=["TC15"],
    )
    assert active == []


def test_tc15_waiver_names_releasing_owner(tmp_path):
    active, waived = check(
        tmp_path,
        """
        def register(self, sid, q):
            self.pending[sid] = q  # tunnelcheck: disable=TC15  released by the reader task's RES_END arm
        """,
        filename=ENG_FIXTURE,
        rules=["TC15"],
    )
    assert active == []
    assert rules_of(waived) == ["TC15"]


def test_tc15_detached_stream_registry_journal_leak(tmp_path):
    """ISSUE 13: the detached-stream registry is in TC15's vocabulary.
    This fixture reconstructs the journal-leak shape — a stream
    registered for resume whose grace-expiry/consumer-gone path never
    releases it: the replay journal's bytes stay resident forever for a
    stream nobody can resume (and the consumer closing the generator at
    the yield is exactly how the path is reached)."""
    active, _ = check(
        tmp_path,
        """
        async def park_for_resume(self, relay, queue):
            self._detached[relay.token] = relay
            while True:
                chunk = await queue.get()
                if chunk is None:
                    return
                relay.journal.append(chunk)
                yield chunk
        """,
        filename=ENG_FIXTURE,
        rules=["TC15"],
    )
    assert rules_of(active) == ["TC15"]
    assert "_detached" in active[0].message


def test_tc15_detached_stream_registry_finally_release_is_clean(tmp_path):
    active, _ = check(
        tmp_path,
        """
        async def park_for_resume(self, relay, queue):
            self._detached[relay.token] = relay
            try:
                while True:
                    chunk = await queue.get()
                    if chunk is None:
                        return
                    relay.journal.append(chunk)
                    yield chunk
            finally:
                self._detached.pop(relay.token, None)
        """,
        filename=ENG_FIXTURE,
        rules=["TC15"],
    )
    assert active == []


# ---------------------------------------------------------------------------
# TC16 — flight/postmortem schema registries + ops routing via ops_route
# ---------------------------------------------------------------------------


def test_tc16_flags_unknown_flight_field(tmp_path):
    # The registry resolves from the REPO's own utils/flight.py even when
    # the fixture tree doesn't carry a copy (the TC06 fallback pattern).
    active, _ = check(
        tmp_path,
        """
        def loop_tick(flight):
            flight.record_iteration(queue_depth=3, queue_dept=4)
        """,
        rules=["TC16"],
    )
    assert rules_of(active) == ["TC16"]
    assert "queue_dept" in active[0].message
    assert "FLIGHT_SCHEMA" in active[0].message


def test_tc16_declared_flight_fields_are_clean(tmp_path):
    active, _ = check(
        tmp_path,
        """
        def loop_tick(flight):
            flight.record_iteration(
                queue_depth=3, budget_tokens=64, decode_steps=8,
            )
        """,
        rules=["TC16"],
    )
    assert active == []


def test_tc16_flags_undeclared_postmortem_extra_key(tmp_path):
    active, _ = check(
        tmp_path,
        """
        def on_incident(bb):
            bb.capture("manual", extra={"trigger": "x", "vibes": 1})
        """,
        rules=["TC16"],
    )
    assert rules_of(active) == ["TC16"]
    assert "vibes" in active[0].message
    assert "POSTMORTEM_SCHEMA" in active[0].message


def test_tc16_flags_handrolled_ops_path_matching_in_endpoints(tmp_path):
    # All three hand-rolled shapes the pre-ISSUE-9 copies used: equality,
    # startswith, and a raw query-token membership test against .path.
    active, _ = check(
        tmp_path,
        """
        async def handler(req):
            if req.path == "/healthz":
                return 1
            if req.path.startswith("/metrics"):
                return 2
            if "trace=1" in req.path:
                return 3
        """,
        filename="p2p_llm_tunnel_tpu/endpoints/custom_ops.py",
        rules=["TC16"],
    )
    assert rules_of(active) == ["TC16", "TC16", "TC16"]
    assert "ops_route" in active[0].message


def test_tc16_ops_route_flag_set_and_non_endpoint_files_are_clean(tmp_path):
    # The sanctioned pattern — flags tested against ops_route's returned
    # set — and the same strings outside endpoints/ (tests, scripts,
    # client-side fetch paths) are out of scope.
    active, _ = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.endpoints.http11 import ops_route

        async def handler(req):
            route = ops_route(req.method, req.path)
            if route is not None and "trace=1" in route[1]:
                return 1
        """,
        filename="p2p_llm_tunnel_tpu/endpoints/custom_ops.py",
        rules=["TC16"],
    )
    assert active == []
    active, _ = check(
        tmp_path,
        """
        async def scrape(fetch):
            return await fetch("/healthz?trace=1")

        def assert_path(path):
            assert path == "/healthz"
        """,
        filename="scripts/poker.py",
        rules=["TC16"],
    )
    assert active == []


def test_tc16_http11_is_the_one_legal_matcher_and_waiver_works(tmp_path):
    # ops_route's own implementation necessarily string-matches.
    active, _ = check(
        tmp_path,
        """
        def ops_route(method, path):
            base = path.partition("?")[0]
            if base not in ("/healthz", "/metrics"):
                return None
            return base[1:]
        """,
        filename="p2p_llm_tunnel_tpu/endpoints/http11.py",
        rules=["TC16"],
    )
    assert active == []
    active, waived = check(
        tmp_path,
        """
        async def handler(req):
            if req.path == "/healthz":  # tunnelcheck: disable=TC16  fixture
                return 1
        """,
        filename="p2p_llm_tunnel_tpu/endpoints/custom_ops.py",
        rules=["TC16"],
    )
    assert active == [] and rules_of(waived) == ["TC16"]


def test_tc16_flags_unknown_startup_span_attr_and_compile_event_field(
        tmp_path):
    """ISSUE 40: the start-up journal's vocabulary is STARTUP_SCHEMA's —
    the attrs of a ``startup.*`` span (the clock's t0/t1/t are no attrs)
    and the keywords of a compile event."""
    active, _ = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.utils.flight import global_compile_watch

        def start(t0):
            global_compile_watch.add_span(
                "startup.backend", t0=t0, platform="tpu", platfrom="tpu")
            global_compile_watch.add_event("startup.ready", t=t0, redy=1)
            with global_compile_watch.startup_phase(
                    "startup.tokenizer", entrys=3):
                pass
            global_compile_watch.note(
                program="decode", key="k", shape=[], seconds=0.1,
                phase="aot", compile_secs=0.1)
        """,
        rules=["TC16"],
    )
    assert rules_of(active) == ["TC16"] * 4
    for violation, typo in zip(active, ("platfrom", "redy", "entrys",
                                        "compile_secs")):
        assert typo in violation.message
        assert "STARTUP_SCHEMA" in violation.message


def test_tc16_declared_startup_fields_and_other_recorders_are_clean(
        tmp_path):
    active, _ = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.utils.flight import global_compile_watch
        from p2p_llm_tunnel_tpu.utils.tracing import global_tracer

        def start(t0, journal, notes):
            global_compile_watch.add_span(
                "startup.backend", t0=t0, t1=t0 + 1.0, platform="tpu",
                device_kind="TPU v5 lite", devices=1)
            with global_compile_watch.startup_phase(
                    "startup.tokenizer", loader="tokenizers") as attrs:
                attrs["entries"] = 3
            global_compile_watch.note(
                program="decode", key="k", shape=[], seconds=0.1,
                phase="aot", aot_hit=False, trace_lower_s=0.05,
                compile_s=0.05, persistent_hit=None)
            # the span recorder's own keywords are not journal fields ...
            global_tracer.add_span("engine.request", trace_id="ab",
                                   t0=t0, track="engine", attrs={"x": 1})
            # ... nor is some other object's note()
            notes.note(anything="goes")
        """,
        rules=["TC16"],
    )
    assert active == []


def test_tc09_checks_the_startup_journals_span_names(tmp_path):
    """The journal writes through its own add_span / add_event /
    startup_phase: their literal names are SPAN_CATALOG's too."""
    active, _ = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.utils.flight import global_compile_watch

        def start(t0):
            global_compile_watch.add_span("startup.backend", t0=t0)
            global_compile_watch.add_span("startup.bakend", t0=t0)
            with global_compile_watch.startup_phase("startup.tokeniser"):
                pass
        """,
        rules=["TC09"],
    )
    assert rules_of(active) == ["TC09", "TC09"]
    assert "startup.bakend" in active[0].message
    assert "startup.tokeniser" in active[1].message


def test_tc16_runtime_registry_agrees_with_static_rule():
    """The runtime guard TC16 statically mirrors: record_iteration
    rejects undeclared fields, capture builds exactly the declared
    schema (both raise loudly on drift)."""
    from p2p_llm_tunnel_tpu.utils.flight import (
        FLIGHT_SCHEMA,
        POSTMORTEM_SCHEMA,
        BlackBox,
        FlightRecorder,
    )

    rec = FlightRecorder(capacity=4)
    with pytest.raises(ValueError):
        rec.record_iteration(not_a_field=1)  # tunnelcheck: disable=TC16  deliberate drift: pins the runtime guard
    rec.record_iteration(**{k: 0 for k in FLIGHT_SCHEMA if k != "iter"})
    bundle = BlackBox(directory="").capture("manual")
    assert set(bundle) == set(POSTMORTEM_SCHEMA)
    # the start-up journal's twin (ISSUE 40): tests/test_flight.py
    # test_startup_journal_rejects_a_field_outside_its_schema


# ---------------------------------------------------------------------------
# SARIF export, --list-rules pin, TC00 counting, parallel + changed-only
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# TC17 — dispatch-site program kinds must be warmup-plan-reachable
# ---------------------------------------------------------------------------


def test_tc17_flags_unwarmed_dispatch_kind(tmp_path):
    """The width-hint hole class one layer earlier: a program kind that
    exists only at a dispatch site cannot even be enumerated by the
    warmup plan — the first request reaching it cold-compiles mid-serve."""
    active, _ = check(
        tmp_path,
        """
        class Eng:
            def warmup_plan(self):
                return [("decode", (128, 8))]

            def _dispatch_chunk_rows(self, rows, t):
                self._note_program("chunk", (t, 128), 0.1)
        """,
        rules=["TC17"],
    )
    assert rules_of(active) == ["TC17"]
    assert "'chunk'" in active[0].message


def test_tc17_plan_tuple_and_warm_helper_kinds_are_reachable(tmp_path):
    """Both warm spellings count: a ("kind", shape) tuple in the plan
    enumeration AND a _warm_* helper's own _note_program call."""
    active, _ = check(
        tmp_path,
        """
        class Eng:
            def warmup_plan(self):
                return [("decode", (128, 8)), ("chunk", (8, 16, 128))]

            def _warm_ragged_program(self, tot):
                self._note_program("ragged", (tot,), 0.0)

            def _dispatch_decode(self):
                self._note_program("decode", (128, 8), 0.1)

            def _dispatch_chunk_rows(self, rows, t):
                self._note_program("chunk", (len(rows), t, 128), 0.1)

            def _dispatch_ragged_rows(self, rows):
                self._note_program("ragged", (64,), 0.1)
        """,
        rules=["TC17"],
    )
    assert active == []


_TC17_ROW_KEY = """
class Eng:
    def warmup_plan(self):
        return [("decode", (128, 8))] + [
            ("chunk", {plan}) for rows in (1, 8)]

    def _warm_chunk_program(self, nb, t, view):
        self._note_program("chunk", {warm}, 0.0)

    def _dispatch_decode(self):
        self._note_program("decode", (128, 8), 0.1)

    def _dispatch_chunk_rows(self, rows, t):
        self._note_program("chunk", {dispatch}, 0.1)
"""


@pytest.mark.parametrize("plan,warm,dispatch,flagged", [
    ("(rows, 16, 128)", "(nb, t, view)", "(nb, t, 128)", False),
    # the key before the row ladder, left behind at the dispatch site
    ("(rows, 16, 128)", "(nb, t, view)", "(t, 128)", True),
    # ... or left behind in the plan and the warmer: one rung of many warmed
    ("(16, 128)", "(t, view)", "(nb, t, 128)", True),
    # a shape that is no tuple literal says nothing about its dimensions
    ("(rows, 16, 128)", "(nb, t, view)", "shape", False),
    ("shape", "shape", "(nb, t, 128)", False),
])
def test_tc17_knows_a_key_by_its_dimensions_too(tmp_path, plan, warm,
                                                dispatch, flagged):
    """ISSUE 30: a chunk program is (rows, t, view).  A dispatch site that
    notes another number of dimensions than warm-up readies mints keys
    that never match, whatever the kind says."""
    active, _ = check(
        tmp_path,
        _TC17_ROW_KEY.format(plan=plan, warm=warm, dispatch=dispatch),
        rules=["TC17"],
    )
    if not flagged:
        assert active == []
        return
    assert rules_of(active) == ["TC17"]
    assert "'chunk'" in active[0].message
    assert "dimension" in active[0].message


def test_tc17_program_key_spelling_is_a_dispatch_site_too(tmp_path):
    """Minting a key via _program_key directly (ad-hoc accounting without
    _note_program) is the same reachability hole — both spellings count."""
    active, _ = check(
        tmp_path,
        """
        class Eng:
            def warmup_plan(self):
                return [("decode", (128, 8))]

            def _dispatch_embed(self, rows):
                key = _program_key("embed", (len(rows),))
                self._ready.add(key)
        """,
        rules=["TC17"],
    )
    assert rules_of(active) == ["TC17"]
    assert "'embed'" in active[0].message


def test_tc17_ifexp_branches_checked_individually(tmp_path):
    """The `"prefill_echo" if echo else "prefill"` dispatch shape: the
    warmed branch must not launder the unwarmed one."""
    active, _ = check(
        tmp_path,
        """
        class Eng:
            def _warm_prefill_program(self, w):
                self._note_program("prefill", (w,), 0.0)

            def _dispatch_prefill_batch(self, runs, t, echo):
                self._note_program(
                    "prefill_echo" if echo else "prefill", (t,), 0.1
                )
        """,
        rules=["TC17"],
    )
    assert rules_of(active) == ["TC17"]
    assert "'prefill_echo'" in active[0].message


def test_tc17_waiver_and_out_of_scope_files(tmp_path):
    """A waiver naming the first-use contract suppresses; files that never
    call _note_program are out of scope entirely."""
    active, waived = check(
        tmp_path,
        """
        class Eng:
            def _dispatch_prefill_batch(self, runs, t, echo):
                self._note_program("prefill_echo", (t,), 0.1)  # tunnelcheck: disable=TC17  eval-only feature, first-use compile by contract
        """,
        rules=["TC17"],
    )
    assert active == [] and rules_of(waived) == ["TC17"]
    active, _ = check(
        tmp_path,
        """
        def unrelated():
            plan = [("decode", (128, 8))]
            return plan
        """,
        filename="clean.py",
        rules=["TC17"],
    )
    assert active == []


def test_tc17_warm_closure_inside_dispatcher_does_not_launder(tmp_path):
    """A warm-NAMED closure nested inside a dispatch function is not a
    plan generator — its literals must not mark the kind reachable (and
    its own _note_program call is a second unwarmed dispatch site)."""
    active, _ = check(
        tmp_path,
        """
        class Eng:
            def _dispatch_spec(self):
                def _warm_fake():
                    self._note_program("spec", (128,), 0.0)
                self._note_program("spec", (128,), 0.1)
        """,
        rules=["TC17"],
    )
    assert rules_of(active) == ["TC17", "TC17"]


def test_tc17_engine_self_run_has_only_the_echo_waiver():
    """The real engine is TC17-clean modulo the documented prefill_echo
    first-use contract — the ragged/chunk/decode/spec/prefill kinds are
    all reachable from warmup_plan()."""
    eng = REPO_ROOT / "p2p_llm_tunnel_tpu" / "engine" / "engine.py"
    active, waived = run_paths([eng], rules=["TC17"])
    assert active == []
    assert rules_of(waived) == ["TC17"]
    assert any("prefill_echo" in v.message for v in waived)


# ---------------------------------------------------------------------------
# TC18 — KV page bytes must pass the tier-boundary pin check before splice
# ---------------------------------------------------------------------------

SPILL_FIXTURE = "p2p_llm_tunnel_tpu/engine/fixture_spill.py"


def test_tc18_unchecked_page_in_splice_flags(tmp_path):
    """The incident shape: a spill-tier page body spliced straight into
    the pool — int4 bytes landing in an int8 pool decode garbage long
    after the splice."""
    active, _ = check(
        tmp_path,
        """
        def splice(self, items):
            for key, idx, page in items:
                payload = page.payload
                self._pool = self._page_in_op(self._pool, idx, payload)
        """,
        filename=SPILL_FIXTURE,
        rules=["TC18"],
    )
    assert rules_of(active) == ["TC18"]
    assert "verify_page_pin" in active[0].message


def test_tc18_pin_check_reassign_launders(tmp_path):
    """The sanctioned idiom: the checked value REPLACES the unchecked
    binding, so the splice can only see the laundered name."""
    active, _ = check(
        tmp_path,
        """
        def splice(self, items):
            for key, idx, page in items:
                payload = page.payload
                payload = verify_page_pin(payload, page.meta, self._meta)
                self._pool = self._page_in_op(self._pool, idx, payload)
        """,
        filename=SPILL_FIXTURE,
        rules=["TC18"],
    )
    assert active == []


def test_tc18_is_flow_sensitive_not_call_anywhere(tmp_path):
    """A bare verify_page_pin CALL whose result is discarded does not
    launder: the unchecked binding still reaches the splice.  (TC14's
    flow-insensitive lattice cannot make this distinction — the rule's
    reason to exist on the CFG-ordered walk.)"""
    active, _ = check(
        tmp_path,
        """
        def splice(self, items):
            for key, idx, page in items:
                payload = page.payload
                verify_page_pin(payload, page.meta, self._meta)
                self._pool = self._page_in_op(self._pool, idx, payload)
        """,
        filename=SPILL_FIXTURE,
        rules=["TC18"],
    )
    assert rules_of(active) == ["TC18"]


def test_tc18_failed_check_path_excluded_from_join(tmp_path):
    """The engine's page-in loop shape: the except handler drops the page
    to the re-prefill fallback via ``continue``, so its tainted state
    never merges past the try — the splice after it is clean."""
    active, _ = check(
        tmp_path,
        """
        def splice(self, items):
            for key, idx, page in items:
                payload = page.payload
                if self._chaos:
                    payload = dict(page.payload)
                try:
                    payload = verify_page_pin(payload, page.meta, self._m)
                except PagePinError:
                    log.warning("dropped %s", key)
                    continue
                self._pool = self._page_in_op(self._pool, idx, payload)
        """,
        filename=SPILL_FIXTURE,
        rules=["TC18"],
    )
    assert active == []


def test_tc18_payload_param_seeds_and_update_sink(tmp_path):
    """A raw page body crossing a function boundary stays tainted, and
    the jax scatter primitive + .at[].set buffer writes are sinks."""
    active, _ = check(
        tmp_path,
        """
        import jax

        def splice(pool, idx, payload):
            pool = jax.lax.dynamic_update_index_in_dim(
                pool, payload, idx, axis=1
            )
            return pool.at[idx].set(payload)
        """,
        filename=SPILL_FIXTURE,
        rules=["TC18"],
    )
    assert rules_of(active) == ["TC18", "TC18"]
    assert any("dynamic_update_index_in_dim" in v.message for v in active)
    assert any(".at[...].set" in v.message for v in active)


def test_tc18_waiver(tmp_path):
    active, waived = check(
        tmp_path,
        """
        def warm(self):
            page = self.frame.payload
            self._pool = self._page_in_op(self._pool, 0, page)  # tunnelcheck: disable=TC18  loop-local round-trip, never left this process
        """,
        filename=SPILL_FIXTURE,
        rules=["TC18"],
    )
    assert active == []
    assert rules_of(waived) == ["TC18"]


def test_tc18_engine_and_prefix_cache_self_run_clean():
    """The real splice paths are TC18-clean WITHOUT waivers: every
    page-in routes through verify_page_pin before touching the pool."""
    eng = REPO_ROOT / "p2p_llm_tunnel_tpu" / "engine" / "engine.py"
    pfx = REPO_ROOT / "p2p_llm_tunnel_tpu" / "engine" / "prefix_cache.py"
    active, waived = run_paths([eng, pfx], rules=["TC18"])
    assert active == []
    assert rules_of(waived) == []


def test_sarif_2_1_0_shape(tmp_path):
    """Pins the SARIF 2.1.0 shape downstream consumers ingest: version,
    $schema, the rules table (ruleIndex points into it), physical
    locations with SRCROOT-relative URIs, and waived findings carried as
    suppressed results."""
    import json

    from tools.tunnelcheck.core import RULE_SUMMARIES

    bad = tmp_path / "bad.py"
    bad.write_text(
        "import time\n\nasync def f():\n    time.sleep(1)\n"
        "\nasync def g():\n    time.sleep(2)  # tunnelcheck: disable=TC01  fixture\n"
    )
    out = tmp_path / "artifacts" / "lint.sarif"
    rc = tunnelcheck_main([str(bad), "--sarif", str(out)])
    assert rc == 1
    log = json.loads(out.read_text())

    assert log["version"] == "2.1.0"
    assert log["$schema"].endswith("sarif-2.1.0.json")
    run = log["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "tunnelcheck"
    rule_ids = [r["id"] for r in driver["rules"]]
    assert rule_ids == sorted(RULE_SUMMARIES)
    assert all(r["shortDescription"]["text"] for r in driver["rules"])

    results = run["results"]
    assert len(results) == 2  # one active, one suppressed
    active = [r for r in results if "suppressions" not in r]
    waived = [r for r in results if "suppressions" in r]
    assert len(active) == 1 and len(waived) == 1
    res = active[0]
    assert res["ruleId"] == "TC01"
    assert rule_ids[res["ruleIndex"]] == "TC01"
    assert res["level"] == "error"
    assert res["message"]["text"]
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("bad.py")
    assert loc["artifactLocation"]["uriBaseId"] == "SRCROOT"
    assert loc["region"]["startLine"] == 4
    assert waived[0]["suppressions"][0]["kind"] == "inSource"
    assert run["originalUriBaseIds"]["SRCROOT"]["uri"].startswith("file://")


def test_sarif_includes_tc00(tmp_path):
    import json

    broken = tmp_path / "broken.py"
    broken.write_text("def broken(:\n")
    out = tmp_path / "lint.sarif"
    assert tunnelcheck_main([str(broken), "--sarif", str(out)]) == 1
    log = json.loads(out.read_text())
    assert [r["ruleId"] for r in log["runs"][0]["results"]] == ["TC00"]


def test_list_rules_pinned_against_code_and_readme(capsys):
    """Rule-id drift (docs vs code) fails fast: --list-rules must show
    exactly TC00..TC21, every runnable rule must have a summary, and the
    README rule table must carry a row for every rule."""
    from tools.tunnelcheck.core import RULE_SUMMARIES, all_rules

    assert tunnelcheck_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    listed = [line.split()[0] for line in out.strip().splitlines()]
    assert listed == [f"TC{i:02d}" for i in range(22)]
    assert set(all_rules()) | {"TC00"} == set(RULE_SUMMARIES)

    readme = (REPO_ROOT / "README.md").read_text(encoding="utf-8")
    for rid in RULE_SUMMARIES:
        if rid == "TC00":
            continue  # framework behavior, documented in prose
        assert f"| {rid}" in readme, f"README rule table is missing {rid}"


def test_tc00_counted_in_summary_and_exit_code(tmp_path, capsys):
    """The ISSUE 11 bugfix pin: an unparseable file must show up in the
    printed summary total AND drive exit code 1 — through the default run,
    a rule filter, and the parallel path — because both are computed from
    the same violation list."""
    (tmp_path / "broken.py").write_text("def broken(:\n")
    (tmp_path / "ok.py").write_text("x = 1\n")

    rc = tunnelcheck_main([str(tmp_path)])
    err = capsys.readouterr().err
    assert rc == 1
    assert "1 violation(s)" in err

    rc = tunnelcheck_main([str(tmp_path), "--rules", "TC06"])
    err = capsys.readouterr().err
    assert rc == 1
    assert "1 violation(s)" in err


def _cli_subprocess(args):
    """Run the real CLI in a clean subprocess.  The parallel paths fork,
    and forking THIS process — pytest with JAX threads already live — is
    exactly what the fork pool must never do in production (the CLI
    process never imports jax); keep the test honest the same way."""
    import subprocess
    import sys

    return subprocess.run(
        [sys.executable, "-m", "tools.tunnelcheck", *args],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120,
    )


def test_tc00_counted_in_summary_with_parallel_jobs(tmp_path):
    (tmp_path / "broken.py").write_text("def broken(:\n")
    (tmp_path / "ok.py").write_text("x = 1\n")
    proc = _cli_subprocess([str(tmp_path), "--jobs", "2"])
    assert proc.returncode == 1
    assert "1 violation(s)" in proc.stderr
    assert "(2 job(s))" in proc.stderr


def test_parallel_jobs_match_serial(tmp_path):
    """--jobs must be a pure speedup: identical findings (waived included),
    identical order."""
    (tmp_path / "a.py").write_text(
        "import time\n\nasync def f():\n    time.sleep(1)\n"
    )
    (tmp_path / "b.py").write_text(
        "import time\n\nasync def g():\n    time.sleep(2)  "
        "# tunnelcheck: disable=TC01  fixture\n"
    )
    (tmp_path / "c.py").write_text("def broken(:\n")
    serial = _cli_subprocess([str(tmp_path), "--show-waived"])
    parallel = _cli_subprocess([str(tmp_path), "--show-waived", "--jobs", "3"])
    assert serial.returncode == parallel.returncode == 1
    assert serial.stdout == parallel.stdout
    lines = serial.stdout.strip().splitlines()
    assert "TC01" in lines[0] and "TC00" in lines[1]  # path-sorted
    assert "[waived]" in lines[2]


def test_restrict_limits_findings_not_context(tmp_path):
    """The --changed-only substrate: findings only for the restricted
    set, while unrestricted files still feed cross-file context (the
    jit-factory below is DEFINED in an unrestricted file and must still
    poison the loop in the restricted one)."""
    factory = tmp_path / "factory.py"
    factory.write_text(
        "import jax\n\ndef make_op():\n    return jax.jit(lambda x: x)\n"
    )
    user = tmp_path / "p2p_llm_tunnel_tpu" / "engine" / "user.py"
    user.parent.mkdir(parents=True)
    user.write_text(
        "from factory import make_op\n\n"
        "def admit(requests):\n"
        "    for req in requests:\n"
        "        make_op()\n"
    )
    bad = tmp_path / "bad.py"
    bad.write_text("import time\n\nasync def f():\n    time.sleep(1)\n")

    full, _ = run_paths([tmp_path])
    assert sorted({v.rule for v in full}) == ["TC01", "TC07"]

    restricted, _ = run_paths([tmp_path], restrict={user.resolve()})
    assert [v.rule for v in restricted] == ["TC07"]
    assert restricted[0].path == user


def test_changed_only_cli_uses_git_answer(tmp_path, capsys, monkeypatch):
    """--changed-only scopes findings to what git reports; a git failure
    degrades to a full run instead of silently reporting clean."""
    import tools.tunnelcheck.__main__ as cli

    bad1 = tmp_path / "bad1.py"
    bad1.write_text("import time\n\nasync def f():\n    time.sleep(1)\n")
    bad2 = tmp_path / "bad2.py"
    bad2.write_text("import time\n\nasync def g():\n    time.sleep(1)\n")

    monkeypatch.setattr(cli, "_git_changed_files",
                        lambda root: {bad1.resolve()})
    rc = cli.main([str(tmp_path), "--changed-only"])
    captured = capsys.readouterr()
    assert rc == 1
    assert "bad1.py" in captured.out and "bad2.py" not in captured.out
    assert "1 changed of 2 file(s)" in captured.err

    monkeypatch.setattr(cli, "_git_changed_files", lambda root: None)
    rc = cli.main([str(tmp_path), "--changed-only"])
    captured = capsys.readouterr()
    assert rc == 1
    assert "bad1.py" in captured.out and "bad2.py" in captured.out


# ---------------------------------------------------------------------------
# Substrate unit tests (dataflow.py / callgraph.py)
# ---------------------------------------------------------------------------


def test_dataflow_augassign_awaiting_value_is_torn():
    """``self._x += await f()`` reads the target, suspends, then stores —
    the torn-increment shape, visible only with evaluation-order events."""
    import ast as ast_mod

    from tools.tunnelcheck.dataflow import FuncCFG, attr_reach

    tree = ast_mod.parse(
        "async def f(self):\n    self._x += await g()\n"
    )
    torn = attr_reach(FuncCFG(tree.body[0]), {"self"})
    assert [(t.obj, t.attr) for t in torn] == [("self", "_x")]


def test_dataflow_try_finally_write_sees_body_reads():
    """A finally-block write observes reads from anywhere in the try body
    (any statement may raise), so a torn RMW cannot hide in a handler."""
    import ast as ast_mod

    from tools.tunnelcheck.dataflow import FuncCFG, attr_reach

    tree = ast_mod.parse(
        "async def f(self):\n"
        "    n = self._x\n"
        "    try:\n"
        "        await g()\n"
        "    finally:\n"
        "        self._x = n + 1\n"
    )
    torn = attr_reach(FuncCFG(tree.body[0]), {"self"})
    assert [(t.obj, t.attr, t.via_local) for t in torn] == [
        ("self", "_x", "n")
    ]


def test_callgraph_transitive_callers_and_factories(tmp_path):
    from tools.tunnelcheck.callgraph import CallGraph
    from tools.tunnelcheck.core import load_source

    f = tmp_path / "mod.py"
    f.write_text(
        "import jax\n\n"
        "def factory():\n    return jax.jit(lambda x: x)\n\n"
        "def middle():\n    return factory()\n\n"
        "def outer():\n    return middle()\n\n"
        "def unrelated():\n    return 1\n"
    )
    sf, err = load_source(f)
    assert err is None
    graph = CallGraph([sf])
    assert graph.functions_calling("jax.jit") == {"factory"}
    closure = graph.transitive_callers(
        lambda n: "jax.jit" in n.dotted_calls, within=f
    )
    assert closure == {"factory", "middle", "outer"}
    assert graph.resolve("outer") is not None
    assert graph.resolve("nope") is None


def test_callgraph_indexes_defs_in_nested_compounds(tmp_path):
    """Coverage regression pin: defs inside except handlers, doubly-nested
    ifs, and loops inside try must be indexed exactly like the full-
    recursion walkers the call graph replaced — a def the graph cannot
    see is a def TC02/TC03/TC07/TC09 silently stop checking."""
    import ast as ast_mod

    from tools.tunnelcheck.callgraph import CallGraph
    from tools.tunnelcheck.core import load_source
    from tools.tunnelcheck.dataflow import iter_functions

    f = tmp_path / "mod.py"
    f.write_text(
        "try:\n"
        "    import fast\n"
        "except ImportError:\n"
        "    def fallback(x):\n"
        "        return x\n"
        "\n"
        "if True:\n"
        "    if True:\n"
        "        def doubly_nested():\n"
        "            pass\n"
        "\n"
        "class C:\n"
        "    try:\n"
        "        def meth(self):\n"
        "            pass\n"
        "    except Exception:\n"
        "        pass\n"
        "\n"
        "for _ in range(1):\n"
        "    def in_loop():\n"
        "        pass\n"
        "\n"
        "match 1:\n"
        "    case 1:\n"
        "        def in_match():\n"
        "            pass\n"
        "    case _:\n"
        "        pass\n"
    )
    sf, err = load_source(f)
    assert err is None
    graph = CallGraph([sf])
    indexed = {id(n.node) for n in graph.by_path[f]}
    for fn, _cls in iter_functions(sf.tree):
        assert id(fn) in indexed, f"call graph missed `{fn.name}`"
    meth = [n for n in graph.by_path[f] if n.name == "meth"]
    assert meth and meth[0].info.is_method  # class context survives nesting


# ---------------------------------------------------------------------------
# TC19 — packed-KV writes only through the byte-aligned helpers
# ---------------------------------------------------------------------------

KV_FIXTURE = "p2p_llm_tunnel_tpu/models/fixture_kv.py"


def test_tc19_direct_packed_write_flags(tmp_path):
    """The incident shape: a pack_int4 result fed straight into a plane
    write at a call site — arbitrary-parity starts clobber the shared
    edge bytes (the bug the spec_ngram x kv-int4 fence hid)."""
    active, _ = check(
        tmp_path,
        """
        def scatter(plane, rows, bpos, vals):
            return plane.at[0, rows, bpos].set(pack_int4(vals, axis=1))
        """,
        filename=KV_FIXTURE,
        rules=["TC19"],
    )
    assert rules_of(active) == ["TC19"]
    assert "splice_packed_rows" in active[0].message


def test_tc19_taint_flows_through_locals(tmp_path):
    active, _ = check(
        tmp_path,
        """
        def scatter(plane, rows, vals):
            packed = pack_int4(vals, axis=1)
            staged = packed
            return plane.at[0, rows].set(staged)
        """,
        filename=KV_FIXTURE,
        rules=["TC19"],
    )
    assert rules_of(active) == ["TC19"]


def test_tc19_hand_rolled_nibble_merge_flags(tmp_path):
    """The pre-helper RMW idiom evades the packer taint by never calling
    pack_int4 — the (hi << 4) | lo shape is flagged on its own."""
    active, _ = check(
        tmp_path,
        """
        def append(plane, idx, slots, bidx, lo, hi):
            return plane.at[idx, slots, bidx].set(
                ((hi << 4) | (lo & 0x0F)).astype(jnp.int8)
            )
        """,
        filename=KV_FIXTURE,
        rules=["TC19"],
    )
    assert rules_of(active) == ["TC19"]
    assert "nibble merge" in active[0].message


def test_tc19_helper_bodies_are_sanctioned(tmp_path):
    """The four audited commit points may (must) do exactly what every
    other function is banned from doing."""
    active, _ = check(
        tmp_path,
        """
        def write_packed_chunk(plane, idx, rows, bpos, vals):
            return plane.at[idx, rows, bpos].set(pack_int4(vals, axis=1))

        def append_packed_token(plane, idx, slots, positions, vals):
            old = plane[idx, slots, positions // 2]
            lo = jnp.where(True, vals, old) & 0x0F
            hi = jnp.where(True, old >> 4, vals)
            return plane.at[idx, slots, positions // 2].set(
                (jnp.left_shift(hi, 4) | lo).astype(jnp.int8)
            )
        """,
        filename=KV_FIXTURE,
        rules=["TC19"],
    )
    assert active == []


def test_tc19_unpacked_writes_and_helper_calls_clean(tmp_path):
    """Raw (unpacked) values into a plane, and UNPACKED values handed to
    an audited helper, are both fine — the helper packs internally."""
    active, _ = check(
        tmp_path,
        """
        def prefill(cache, slots, k, kq):
            cache = cache.at[:, slots].set(k)
            return write_packed_prefix(cache, slots, kq)

        def verify(cache, idx, slots, starts, kq):
            return splice_packed_rows(cache, idx, slots, starts, kq)
        """,
        filename=KV_FIXTURE,
        rules=["TC19"],
    )
    assert active == []


def test_tc19_out_of_scope_tree_ignored(tmp_path):
    active, _ = check(
        tmp_path,
        """
        def scatter(plane, vals):
            return plane.at[0].set(pack_int4(vals, axis=1))
        """,
        filename="experiments/scratch.py",
        rules=["TC19"],
    )
    assert active == []


def test_tc19_waiver_parses(tmp_path):
    active, waived = check(
        tmp_path,
        """
        def scatter(plane, vals):
            return plane.at[0].set(pack_int4(vals, axis=1))  # tunnelcheck: disable=TC19  scale plane, not a packed token plane
        """,
        filename=KV_FIXTURE,
        rules=["TC19"],
    )
    assert active == []
    assert rules_of(waived) == ["TC19"]


def test_tc19_kv_write_paths_self_run_clean():
    """The real packed-write paths are TC19-clean WITHOUT waivers: since
    ISSUE 17 every XLA-path packed write in quant/transformer/engine
    routes through the four byte-aligned helpers."""
    base = REPO_ROOT / "p2p_llm_tunnel_tpu"
    files = [base / "models" / "quant.py",
             base / "models" / "transformer.py",
             base / "engine" / "engine.py",
             base / "engine" / "prefix_cache.py"]
    active, waived = run_paths(files, rules=["TC19"])
    assert active == []
    assert rules_of(waived) == []


# ---------------------------------------------------------------------------
# Interprocedural summary engine (ISSUE 18 tentpole) — unit tests against
# dataflow.interproc_taint directly: transfer functions, fixpoint
# termination, and the depth bound.
# ---------------------------------------------------------------------------


def _interproc_engine(tmp_path, code, *, on_sink_calls=("sink",),
                      max_depth=4):
    """Build an InterprocTaint over one fixture module under a toy policy:
    ``taint_src()`` is THE source, ``clean()`` THE sanitizer, ``sink()``'s
    first argument THE sink."""
    import ast as _ast

    from tools.tunnelcheck.callgraph import CallGraph
    from tools.tunnelcheck.core import load_source
    from tools.tunnelcheck.dataflow import (
        TaintPolicy,
        call_name,
        interproc_taint,
    )

    f = tmp_path / "mod.py"
    f.write_text(textwrap.dedent(code))
    sf, err = load_source(f)
    assert err is None

    def is_source(expr):
        return isinstance(expr, __import__("ast").Call) and \
            call_name(expr) == "taint_src"

    def sink_args(call):
        if call_name(call) in on_sink_calls and call.args:
            return [(call.args[0], f"the `{call_name(call)}` sink")]
        return []

    policy = TaintPolicy(
        is_source=is_source,
        sanitizers=frozenset({"clean"}),
        seed_params=frozenset(),
        sink_args=sink_args,
        sink_assign=lambda node: [],
    )
    graph = CallGraph([sf])
    return interproc_taint(graph, policy, max_depth=max_depth), graph


def _summary(engine, graph, name):
    node = graph.by_name[name][0].node
    s = engine.summary_for(node)
    assert s is not None
    return s


def test_interproc_summary_param_to_return_transfer(tmp_path):
    engine, graph = _interproc_engine(
        tmp_path,
        """
        def ident(x):
            return x

        def fresh(x):
            return 1

        def srcfn():
            return taint_src()

        def laundered(x):
            return clean(x)
        """,
    )
    from tools.tunnelcheck.dataflow import SRC

    assert _summary(engine, graph, "ident").ret == {"x"}
    assert _summary(engine, graph, "fresh").ret == set()
    assert _summary(engine, graph, "srcfn").ret == {SRC}
    # The sanitizer's RESULT is clean whatever it read: the registered-
    # sanitizer contract, applied at the summary level.
    assert _summary(engine, graph, "laundered").ret == set()


def test_interproc_sink_params_and_cross_function_report(tmp_path):
    engine, graph = _interproc_engine(
        tmp_path,
        """
        def stamp(v):
            sink(v)

        def top():
            stamp(taint_src())
        """,
    )
    s = _summary(engine, graph, "stamp")
    assert set(s.sink_params) == {"v"}
    hits = []
    engine.analyze(graph.by_name["top"][0].node,
                   on_sink=lambda node, d: hits.append((node.lineno, d)))
    assert len(hits) == 1
    # The report lands at top's CALL to stamp and names the chain.
    assert "via `stamp()`" in hits[0][1]


def test_interproc_fixpoint_terminates_on_mutual_recursion(tmp_path):
    engine, graph = _interproc_engine(
        tmp_path,
        """
        def ping(x):
            return pong(x)

        def pong(x):
            if x:
                return ping(x)
            return x

        def forever_a(x):
            return forever_b(x)

        def forever_b(x):
            return forever_a(x)
        """,
    )
    # Monotone-from-empty: summaries only grow, so the iteration stops at
    # the fixpoint within the depth bound instead of chasing the cycle.
    assert engine.rounds <= engine.max_depth
    # A cycle with NO base case never returns its argument — the empty
    # summary is the semantically correct answer, not a missed fact.
    assert _summary(engine, graph, "forever_a").ret == set()
    # A cycle WITH a base case transfers its parameter through both hops.
    assert _summary(engine, graph, "ping").ret == {"x"}
    assert _summary(engine, graph, "pong").ret == {"x"}


def test_interproc_depth_bound_caps_chain_length(tmp_path):
    chain = """
        def h5(x):
            return x

        def h4(x):
            return h5(x)

        def h3(x):
            return h4(x)

        def h2(x):
            return h3(x)

        def h1(x):
            return h2(x)
        """
    shallow, graph_s = _interproc_engine(tmp_path, chain, max_depth=2)
    assert _summary(shallow, graph_s, "h1").ret == set()
    deep, graph_d = _interproc_engine(tmp_path, chain, max_depth=8)
    assert _summary(deep, graph_d, "h1").ret == {"x"}
    # 5 hops resolve in ~5 rounds + 1 no-change round, never the full 8.
    assert deep.rounds <= 7


# ---------------------------------------------------------------------------
# TC20 — extracted page bytes must pass verify_page_pin before any
# tunnel send / tier write / splice (interprocedural)
# ---------------------------------------------------------------------------


def test_tc20_extracted_page_sent_flags(tmp_path):
    active, _ = check(
        tmp_path,
        """
        def evict(self, idx):
            page = self._page_out_op(self._pool, idx)
            self._link.send_bytes(page)
        """,
        filename=SPILL_FIXTURE,
        rules=["TC20"],
    )
    assert rules_of(active) == ["TC20"]
    assert "verify_page_pin" in active[0].message


def test_tc20_cross_function_laundering_flags_at_call_site(tmp_path):
    """The boundary-crossing shape TC18 cannot see: extraction in one
    function, the send hidden inside a helper."""
    active, _ = check(
        tmp_path,
        """
        class Tier:
            def ship(self, link, page):
                link.send_bytes(page)

            def evict(self, link, idx):
                page = self._page_out_op(self._pool, idx)
                self.ship(link, page)
        """,
        filename=SPILL_FIXTURE,
        rules=["TC20"],
    )
    assert rules_of(active) == ["TC20"]
    assert "via `ship()`" in active[0].message
    assert "self.ship(link, page)" in (tmp_path / SPILL_FIXTURE).read_text(
    ).splitlines()[active[0].line - 1]


def test_tc20_cross_function_sanitizer_clears(tmp_path):
    """verify_page_pin inside a helper launders for every caller: the
    summary records the cleared return, not the raw parameter."""
    active, _ = check(
        tmp_path,
        """
        class Tier:
            def pin(self, page):
                return verify_page_pin(page, self._meta, self._want)

            def evict(self, link, idx):
                page = self._page_out_op(self._pool, idx)
                link.send_bytes(self.pin(page))
        """,
        filename=SPILL_FIXTURE,
        rules=["TC20"],
    )
    assert active == []


def test_tc20_call_graph_cycle_terminates_and_flags(tmp_path):
    active, _ = check(
        tmp_path,
        """
        class Tier:
            def hop_a(self, link, page, n):
                if n:
                    self.hop_b(link, page, n - 1)
                link.send_bytes(page)

            def hop_b(self, link, page, n):
                self.hop_a(link, page, n)

            def evict(self, link, idx):
                page = self._page_out_op(self._pool, idx)
                self.hop_b(link, page, 2)
        """,
        filename=SPILL_FIXTURE,
        rules=["TC20"],
    )
    assert rules_of(active) == ["TC20"]


def test_tc20_payload_receiver_heuristic(tmp_path):
    """``spill_page.payload`` is page bytes; ``msg.payload`` is frame
    plumbing — only receivers named like pages seed the taint, so the
    signaling/frames layer's ubiquitous payload fields stay silent."""
    active, _ = check(
        tmp_path,
        """
        def drain(self, spill_page, key):
            self._index.note_spilled(key, spill_page.payload)

        def pump(self, msg):
            self._link.send_bytes(msg.payload)
        """,
        filename=SPILL_FIXTURE,
        rules=["TC20"],
    )
    assert rules_of(active) == ["TC20"]
    assert active[0].message.count("tier write") == 1


def test_tc20_waiver_and_out_of_scope(tmp_path):
    code = """
        def evict(self, idx):
            page = self._page_out_op(self._pool, idx)
            self._link.send_bytes(page)  # tunnelcheck: disable=TC20  loopback self-test: bytes re-enter this process through the same pins
        """
    active, waived = check(tmp_path, code, filename=SPILL_FIXTURE,
                           rules=["TC20"])
    assert active == []
    assert rules_of(waived) == ["TC20"]
    active, _ = check(tmp_path, code, filename="elsewhere/spill.py",
                      rules=["TC20"])
    assert active == []


def test_tc20_meta_fixture_stripped_real_chain_flags():
    """Acceptance meta-fixture: take the ENGINE'S real page-in chain
    (_spill_copy_in), strip the verify_page_pin reassignment, and TC20
    must fire — proof the rule guards the production shape, not a toy.
    The unstripped copy is the control: clean with zero waivers."""
    import ast as _ast
    import tempfile

    src = (REPO_ROOT / "p2p_llm_tunnel_tpu" / "engine" / "engine.py"
           ).read_text(encoding="utf-8")
    fn = next(
        n for n in _ast.walk(_ast.parse(src))
        if isinstance(n, _ast.FunctionDef) and n.name == "_spill_copy_in"
    )

    with tempfile.TemporaryDirectory() as td:
        active, _ = check(Path(td), _ast.unparse(fn),
                          filename=SPILL_FIXTURE, rules=["TC20"])
        assert active == [], "the real chain must be clean as shipped"

    class StripPin(_ast.NodeTransformer):
        def visit_Assign(self, node):
            if (isinstance(node.value, _ast.Call)
                    and isinstance(node.value.func, _ast.Name)
                    and node.value.func.id == "verify_page_pin"):
                return None
            return node

    stripped = _ast.fix_missing_locations(StripPin().visit(fn))
    with tempfile.TemporaryDirectory() as td:
        active, _ = check(Path(td), _ast.unparse(stripped),
                          filename=SPILL_FIXTURE, rules=["TC20"])
        assert rules_of(active) == ["TC20"]
        assert "splice" in active[0].message


def test_tc20_registries_match_runtime():
    """Runtime agreement: the sanitizer TC20 credits and the extraction /
    tier-write names it watches are the REAL prefix_cache symbols — the
    static model cannot drift from what the runtime enforces."""
    from p2p_llm_tunnel_tpu.engine import prefix_cache
    from tools.tunnelcheck import rules_tierpin as rt

    for name in rt.SANITIZERS:
        assert callable(getattr(prefix_cache, name)), name
    assert hasattr(prefix_cache.PrefixIndex, "export_state")
    for name in rt.TIER_WRITE_CALLS:
        assert callable(getattr(prefix_cache.PrefixIndex, name)), name


def test_tc20_send_registry_covers_kv_pages_wire_path():
    """ISSUE 20 agreement: the KV_PAGES transfer framer the runtime uses
    to put pool bytes on the wire is a registered TC20 send sink — an
    unpinned export cannot reach a transfer frame even when the actual
    ``channel.send`` of the encoded frame lives in another function —
    and the registered name IS the runtime symbol."""
    from p2p_llm_tunnel_tpu.protocol import frames
    from tools.tunnelcheck import rules_tierpin as rt

    assert "kv_pages_chunk" in rt.SEND_CALLS
    assert callable(getattr(frames.TunnelMessage, "kv_pages_chunk"))
    for mt in ("KV_PAGES_HDR", "KV_PAGES_CHUNK", "KV_PAGES_END",
               "KV_PAGES_ACK"):
        assert hasattr(frames.MessageType, mt)


def test_tc20_unpinned_bytes_into_kv_pages_chunk_flag(tmp_path):
    """Pool bytes that skip verify_page_pin must not enter a KV_PAGES
    frame: the framer itself is the sink, so the violation lands in the
    function that builds the frame, not wherever the send happens."""
    active, _ = check(
        tmp_path,
        """
        from p2p_llm_tunnel_tpu.protocol.frames import TunnelMessage

        def ship(pool, op, sid):
            raw = op.page_out(pool, 3)
            return TunnelMessage.kv_pages_chunk(sid, raw)
        """,
        filename=SPILL_FIXTURE,
        rules=["TC20"],
    )
    assert rules_of(active) == ["TC20"]
    assert "send" in active[0].message


def test_tc20_engine_and_prefix_cache_self_run():
    """The shipped extraction->boundary paths pass TC20 with only the
    documented warmup waiver (engine.py's compile round-trip)."""
    eng = REPO_ROOT / "p2p_llm_tunnel_tpu" / "engine" / "engine.py"
    pfx = REPO_ROOT / "p2p_llm_tunnel_tpu" / "engine" / "prefix_cache.py"
    active, waived = run_paths([eng, pfx], rules=["TC20"])
    assert active == []
    assert rules_of(waived) == ["TC20"]


# ---------------------------------------------------------------------------
# TC21 — interprocedural header taint (TC14 across function boundaries)
# ---------------------------------------------------------------------------

TAINT21_FIXTURE = "p2p_llm_tunnel_tpu/endpoints/fixture_taint21.py"


def test_tc21_extraction_helper_flags_at_call_site(tmp_path):
    """The pre-PR-7 minting hole one call deep: a helper RETURNS the raw
    header value, so TC14's flat lattice sees a clean call result."""
    active, _ = check(
        tmp_path,
        """
        def grab(req):
            return req.headers.get("x-tunnel-tenant", "")

        def admit(req, sched):
            sched.tenant_begin(grab(req))
        """,
        filename=TAINT21_FIXTURE,
        rules=["TC14", "TC21"],
    )
    assert rules_of(active) == ["TC21"]
    assert "helper" in active[0].message


def test_tc21_stamping_helper_flags_at_call_site(tmp_path):
    """The dual shape: the SINK hides inside the helper."""
    active, _ = check(
        tmp_path,
        """
        def stamp(kw, raw):
            kw["tenant"] = raw

        def admit(req, kw):
            stamp(kw, req.headers.get("x-tunnel-tenant", ""))
        """,
        filename=TAINT21_FIXTURE,
        rules=["TC14", "TC21"],
    )
    assert rules_of(active) == ["TC21"]


def test_tc21_sanitized_helper_is_clean(tmp_path):
    active, _ = check(
        tmp_path,
        """
        def grab(req):
            return parse_tenant(req.headers.get("x-tunnel-tenant", ""))

        def admit(req, sched):
            sched.tenant_begin(grab(req))
        """,
        filename=TAINT21_FIXTURE,
        rules=["TC14", "TC21"],
    )
    assert active == []


def test_tc21_does_not_duplicate_tc14_findings(tmp_path):
    """Same-line flows belong to TC14; TC21 reporting them too would
    double every waiver in the tree."""
    active, _ = check(
        tmp_path,
        """
        def admit(req, sched):
            sched.tenant_begin(req.headers.get("x-tunnel-tenant", ""))
        """,
        filename=TAINT21_FIXTURE,
        rules=["TC14", "TC21"],
    )
    assert rules_of(active) == ["TC14"]


def test_tc21_waiver_and_cycle(tmp_path):
    active, waived = check(
        tmp_path,
        """
        def bounce(req, sched, n):
            if n:
                relay(req, sched, n - 1)
            return req.headers.get("x-t", "")

        def relay(req, sched, n):
            sched.tenant_begin(bounce(req, sched, n))  # tunnelcheck: disable=TC21  herd-test harness: headers are fixture constants
        """,
        filename=TAINT21_FIXTURE,
        rules=["TC14", "TC21"],
    )
    assert active == []
    assert rules_of(waived) == ["TC21"]


def test_tc21_package_self_run_is_clean():
    pkg = REPO_ROOT / "p2p_llm_tunnel_tpu"
    active, _ = run_paths([pkg], rules=["TC21"])
    assert active == []


# ---------------------------------------------------------------------------
# Per-file result cache (ISSUE 18 satellite)
# ---------------------------------------------------------------------------


def test_cache_cold_then_warm_same_results(tmp_path):
    f = tmp_path / "snippet.py"
    f.write_text(textwrap.dedent(
        """
        import time

        async def handler():
            time.sleep(1)
            time.sleep(2)  # tunnelcheck: disable=TC01  fixture
        """
    ))
    cache = tmp_path / "cache"
    stats_cold: dict = {}
    a_cold, w_cold = run_paths([f], rules=["TC01"], stats=stats_cold,
                               cache_dir=cache)
    assert stats_cold["cache_misses"] == 1
    assert stats_cold["cache_hits"] == 0
    stats_warm: dict = {}
    a_warm, w_warm = run_paths([f], rules=["TC01"], stats=stats_warm,
                               cache_dir=cache)
    assert stats_warm["cache_hits"] == 1
    assert stats_warm["cache_misses"] == 0
    # The warm partition is IDENTICAL, waived findings included.
    assert [(v.rule, v.line) for v in a_warm] == \
        [(v.rule, v.line) for v in a_cold]
    assert [(v.rule, v.line) for v in w_warm] == \
        [(v.rule, v.line) for v in w_cold]


def test_cache_invalidated_by_any_edit(tmp_path):
    """The key commits to the WHOLE tree digest: interprocedural rules
    make per-file isolation unsound, so editing one file must invalidate
    every entry — honest, not clever."""
    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text("x = 1\n")
    b.write_text("y = 2\n")
    cache = tmp_path / "cache"
    run_paths([a, b], rules=["TC01"], stats={}, cache_dir=cache)
    stats: dict = {}
    run_paths([a, b], rules=["TC01"], stats=stats, cache_dir=cache)
    assert stats["cache_hits"] == 2
    b.write_text("y = 3\n")
    stats = {}
    run_paths([a, b], rules=["TC01"], stats=stats, cache_dir=cache)
    assert stats["cache_hits"] == 0
    assert stats["cache_misses"] == 2


def test_cache_keyed_on_selected_rules(tmp_path):
    f = tmp_path / "snippet.py"
    f.write_text("import time\n\nasync def h():\n    time.sleep(1)\n")
    cache = tmp_path / "cache"
    run_paths([f], rules=["TC01"], stats={}, cache_dir=cache)
    stats: dict = {}
    active, _ = run_paths([f], rules=["TC05"], stats=stats, cache_dir=cache)
    assert stats["cache_hits"] == 0  # different rule set, different key
    assert active == []


# ---------------------------------------------------------------------------
# Waiver audit (ISSUE 18 satellite)
# ---------------------------------------------------------------------------


def test_waiver_audit_flags_stale_and_keeps_live(tmp_path):
    f = tmp_path / "snippet.py"
    f.write_text(textwrap.dedent(
        """
        import time

        async def handler():
            time.sleep(1)  # tunnelcheck: disable=TC01  live: suppresses a real finding
            x = 1  # tunnelcheck: disable=TC01  stale: nothing fires here
        """
    ))
    audit: list = []
    active, waived = run_paths([f], rules=["TC01"], waiver_audit=audit)
    assert active == []
    assert rules_of(waived) == ["TC01"]
    assert len(audit) == 1
    path, line, msg = audit[0]
    assert line == 6 and "stale waiver" in msg and "TC01" in msg


def test_waiver_audit_unknown_rule_id_always_reported(tmp_path):
    f = tmp_path / "snippet.py"
    f.write_text("x = 1  # tunnelcheck: disable=TC99  typo'd id\n")
    audit: list = []
    run_paths([f], rules=["TC01"], waiver_audit=audit)
    assert len(audit) == 1
    assert "unknown rule" in audit[0][2] and "TC99" in audit[0][2]


def test_waiver_audit_stale_file_waiver(tmp_path):
    f = tmp_path / "snippet.py"
    f.write_text("# tunnelcheck: disable-file=TC01\nx = 1\n")
    audit: list = []
    run_paths([f], rules=["TC01"], waiver_audit=audit)
    assert len(audit) == 1
    assert "file waiver" in audit[0][2] and audit[0][1] == 1


def test_waiver_audit_skips_unselected_rules(tmp_path):
    """A subset run cannot judge a waiver for a rule it didn't execute —
    silence, not a false stale report."""
    f = tmp_path / "snippet.py"
    f.write_text("x = 1  # tunnelcheck: disable=TC05  judged only when TC05 runs\n")
    audit: list = []
    run_paths([f], rules=["TC01"], waiver_audit=audit)
    assert audit == []
    audit = []
    run_paths([f], rules=["TC05"], waiver_audit=audit)
    assert len(audit) == 1


def test_waiver_audit_shipped_tree_has_no_stale_waivers():
    """Waiver hygiene as an invariant: every `# tunnelcheck: disable=`
    comment in the tree suppresses a finding that actually fires (the
    16 dead comments found when the audit landed are gone)."""
    audit: list = []
    run_paths(
        [REPO_ROOT / "p2p_llm_tunnel_tpu", REPO_ROOT / "scripts",
         REPO_ROOT / "tests", REPO_ROOT / "chip_smoke.py",
         REPO_ROOT / "__graft_entry__.py"],
        waiver_audit=audit,
    )
    assert audit == [], f"stale waivers: {audit}"


# ---------------------------------------------------------------------------
# CLI: wall-time budget + cache/audit plumbing (ISSUE 18 satellite)
# ---------------------------------------------------------------------------


def test_cli_budget_gate(tmp_path, capsys):
    f = tmp_path / "clean.py"
    f.write_text("x = 1\n")
    assert tunnelcheck_main([str(f), "--budget-s", "600"]) == 0
    capsys.readouterr()
    assert tunnelcheck_main([str(f), "--budget-s", "0"]) == 1
    err = capsys.readouterr().err
    assert "exceeded" in err and "budget" in err


def test_cli_cache_and_audit_summary(tmp_path, capsys):
    f = tmp_path / "snippet.py"
    f.write_text("x = 1  # tunnelcheck: disable=TC99  typo\n")
    cache = tmp_path / "cache"
    args = [str(f), "--cache", str(cache), "--waiver-audit"]
    assert tunnelcheck_main(args) == 0
    err = capsys.readouterr().err
    assert "0 hit(s) 1 miss(es)" in err
    assert "1 stale waiver(s)" in err
    assert "waiver-audit: waiver names unknown rule `TC99`" in err
    assert tunnelcheck_main(args) == 0
    err = capsys.readouterr().err
    assert "1 hit(s) 0 miss(es)" in err
    # The audit still reports from the CACHED entry's re-parse.
    assert "1 stale waiver(s)" in err
