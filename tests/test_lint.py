"""repro-lint (tools/lint): every pass has a known-bad fixture that it
flags at the right line and a known-good fixture it leaves alone, the
pragma/baseline layers suppress exactly what they claim to, and the
live tree stays clean against the committed baseline (docs/lint.md)."""
import json
import os
import sys

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from tools.lint import (  # noqa: E402
    lint_source,
    load_baseline,
    run_lint,
    split_baselined,
)
from tools.lint.passes import PASS_BY_NAME  # noqa: E402
from tools.lint.passes import choice_set  # noqa: E402


def _lint(src, pass_name, rel="fixture.py", extra_files=None):
    """Run ONE pass over an in-memory fixture; only fixture findings."""
    findings = lint_source(
        src,
        rel=rel,
        passes=[PASS_BY_NAME[pass_name]],
        root=_ROOT,
        extra_files=extra_files,
    )
    return [f for f in findings if f.file == rel]


# ---------------------------------------------------------------------------
# host-sync (RL001)
# ---------------------------------------------------------------------------

_HOST_SYNC_BAD = """\
import jax
import jax.numpy as jnp

def drive(x):
    s = jax.lax.while_loop(lambda c: c[1], lambda c: c, (x, True))
    live = int(jnp.sum(s[0]))
    frac = jnp.mean(s[0]).item()
    return live, frac
"""

_HOST_SYNC_GOOD = """\
import jax
import jax.numpy as jnp

def drive(x):
    s = jax.lax.while_loop(lambda c: c[1], lambda c: c, (x, True))
    n = int(x.shape[0])
    return n

def helper(y):
    return int(jnp.sum(y))
"""


def test_host_sync_flags_conversions_in_round_loops():
    findings = _lint(_HOST_SYNC_BAD, "host-sync")
    assert [(f.code, f.line) for f in findings] == [("RL001", 6), ("RL001", 7)]


def test_host_sync_ignores_static_shape_reads_and_plain_helpers():
    assert _lint(_HOST_SYNC_GOOD, "host-sync") == []


def test_host_sync_trailing_pragma_suppresses():
    src = _HOST_SYNC_BAD.replace(
        "live = int(jnp.sum(s[0]))",
        "live = int(jnp.sum(s[0]))  # repro-lint: disable=host-sync",
    )
    assert [f.line for f in _lint(src, "host-sync")] == [7]


def test_host_sync_standalone_pragma_covers_next_line():
    src = _HOST_SYNC_BAD.replace(
        "    live = int(jnp.sum(s[0]))",
        "    # repro-lint: disable=host-sync\n    live = int(jnp.sum(s[0]))",
    )
    assert [f.line for f in _lint(src, "host-sync")] == [8]


# ---------------------------------------------------------------------------
# scatter-determinism (RL002)
# ---------------------------------------------------------------------------

_SCATTER_BAD = """\
import jax.numpy as jnp

def sv_round_fns(a, b, n):
    def round_body(D, Q, s):
        idx = jnp.where(D != Q, D, n)
        Q = Q.at[idx].set(s, mode="drop")
        D = D.at[idx].min(Q, mode="drop")
        return D, Q
    return round_body
"""

_SCATTER_GOOD = """\
import jax.numpy as jnp

def round_body(D, idx, vals, n):
    return D.at[idx].min(vals, mode="drop")

def merge_stats(words, s, vals):
    return words.at[s].add(vals)
"""


def test_scatter_flags_set_on_dup_capable_index_once():
    # Exactly ONE finding: round_body is in scope via both its own name
    # and its parent sv_round_fns -- the site must not double-report.
    findings = _lint(_SCATTER_BAD, "scatter-determinism")
    assert [(f.code, f.line) for f in findings] == [("RL002", 6)]


def test_scatter_allows_min_scatters_and_out_of_scope_fns():
    # .at[].min in a round body is the sanctioned min-CRCW form; the
    # .at[].add lives outside any sv/round/hook scope.
    assert _lint(_SCATTER_GOOD, "scatter-determinism") == []


def test_scatter_kernels_dir_is_always_in_scope():
    src = "def pack(buf, idx, v):\n    return buf.at[idx].set(v)\n"
    findings = _lint(src, "scatter-determinism", rel="src/repro/kernels/pack.py")
    assert [(f.code, f.line) for f in findings] == [("RL002", 2)]
    assert _lint(src, "scatter-determinism", rel="src/repro/core/pack.py") == []


# ---------------------------------------------------------------------------
# compat-shim (RL003)
# ---------------------------------------------------------------------------

_COMPAT_BAD = """\
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map
"""

_COMPAT_GOOD = """\
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import Mesh, make_mesh, shard_map
"""


def test_compat_flags_direct_imports_of_drifting_apis():
    findings = _lint(_COMPAT_BAD, "compat-shim")
    assert [(f.code, f.line) for f in findings] == [("RL003", 1), ("RL003", 2)]


def test_compat_allows_stable_homes_and_the_shim():
    assert _lint(_COMPAT_GOOD, "compat-shim") == []


def test_compat_shim_file_itself_is_exempt():
    assert _lint(_COMPAT_BAD, "compat-shim", rel="src/repro/compat.py") == []


def test_compat_disable_file_pragma():
    src = "# repro-lint: disable-file=compat-shim\n" + _COMPAT_BAD
    assert _lint(src, "compat-shim") == []


# ---------------------------------------------------------------------------
# choice-set (RL004)
# ---------------------------------------------------------------------------

_CHOICE_BAD = """\
from repro.core.components import check_choice

def rank(pack_mode="aos"):
    check_choice("pack_mode", pack_mode, ("aos", "soa"))
    check_choice("mystery_knob", pack_mode, PACK_MODES)
"""

_CHOICE_GOOD = """\
from repro.core.components import check_choice
from repro.core.list_ranking import WYLIE_PACK_MODES

def rank(pack_mode="aos"):
    check_choice("pack_mode", pack_mode, WYLIE_PACK_MODES)
"""


def test_choice_set_flags_inline_literals_and_unknown_knobs():
    findings = _lint(_CHOICE_BAD, "choice-set")
    assert [(f.code, f.line) for f in findings] == [("RL004", 4), ("RL004", 5)]
    assert "inline literal" in findings[0].message
    assert "not registered" in findings[1].message


def test_choice_set_accepts_module_constants():
    assert _lint(_CHOICE_GOOD, "choice-set") == []


_MATRIX = """\
# Engines

<!-- choice-matrix -->
| knob | valid values |
|------|--------------|
| `engine=` | `auto` `dense` |
| `pack_mode=` | `aos` `soa` |

# Numeric knobs
| `ghost=` | `x` |
"""


def test_documented_choices_parses_only_the_marked_table():
    assert choice_set.documented_choices(_MATRIX) == {
        "engine": ("auto", "dense"),
        "pack_mode": ("aos", "soa"),
    }


def test_compare_reports_mismatch_missing_and_extra_rows():
    doc = choice_set.documented_choices(_MATRIX)
    code = {"engine": ("auto", "dense", "sparse"), "kind": ("cc",)}
    problems = dict(choice_set.compare(doc, code))
    assert "docs/engines.md says" in problems["engine"]
    assert "no choice-matrix row" in problems["kind"]
    assert "not in the choice-set registry" in problems["pack_mode"]


def test_choice_set_registry_matches_live_docs():
    """The pass reproduces check_docs.py: live code vs live docs."""
    doc = choice_set.documented_choices(
        open(os.path.join(_ROOT, "docs", "engines.md")).read()
    )
    code = choice_set.code_choices(_ROOT)
    assert choice_set.compare(doc, code) == []
    assert len(code) == 12  # the knobs of docs/engines.md's matrix


# ---------------------------------------------------------------------------
# recompile-hazard (RL005)
# ---------------------------------------------------------------------------

_RECOMPILE_BAD = """\
import jax.numpy as jnp

def drive(mask):
    live = int(jnp.sum(mask))
    buf = jnp.zeros(live, dtype=jnp.int32)
    return buf
"""

_RECOMPILE_GOOD = """\
import jax.numpy as jnp
from repro.core.frontier import next_pow2

def drive(mask):
    live = int(jnp.sum(mask))
    size = next_pow2(live)
    buf = jnp.zeros(size, dtype=jnp.int32)
    other = jnp.zeros(next_pow2(live))
    return buf, other
"""

_RECOMPILE_STATIC_BAD = """\
import jax
import jax.numpy as jnp
from functools import partial

@partial(jax.jit, static_argnames=("bound",))
def kernel(x, *, bound):
    return x[:bound]

def drive(x):
    b = int(jnp.max(x))
    return kernel(x, bound=b)
"""


def test_recompile_flags_data_dependent_shapes():
    findings = _lint(_RECOMPILE_BAD, "recompile-hazard")
    assert [(f.code, f.line) for f in findings] == [("RL005", 5)]


def test_recompile_cleared_by_pow2_bucketing():
    assert _lint(_RECOMPILE_GOOD, "recompile-hazard") == []


def test_recompile_flags_tainted_static_argnames():
    findings = _lint(_RECOMPILE_STATIC_BAD, "recompile-hazard")
    assert [(f.code, f.line) for f in findings] == [("RL005", 11)]
    assert "bound=" in findings[0].message


# ---------------------------------------------------------------------------
# block-timer (RL006)
# ---------------------------------------------------------------------------

_TIMER_BAD = """\
import time
import jax

def bench(fn, x):
    t0 = time.perf_counter()
    out = fn(x)
    dt = time.perf_counter() - t0
    t1 = time.monotonic()
    fn(out)
    print("warm")
    return time.monotonic() - t1, dt
"""

_TIMER_GOOD = """\
import time
import jax

def bench(fn, x):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(x))
    dt = time.perf_counter() - t0
    print("done", dt)
    t1 = time.perf_counter()
    emit("name", dt)
    t2 = time.perf_counter()
    return out, t2 - t1

def helper(fn, x):
    def inner(y):
        return fn(y)
    t0 = time.perf_counter()
    res = fn(x)
    res.block_until_ready()
    return time.perf_counter() - t0
"""


def test_block_timer_flags_unblocked_intervals():
    findings = _lint(_TIMER_BAD, "block-timer", rel="benchmarks/fix.py")
    assert [(f.code, f.line) for f in findings] == [("RL006", 7), ("RL006", 11)]
    assert "block_until_ready" in findings[0].message


def test_block_timer_accepts_blocked_intervals_and_host_helpers():
    # blocked work, host-only calls between reads, nested defs as
    # separate timelines, and the .block_until_ready() method form
    assert _lint(_TIMER_GOOD, "block-timer", rel="benchmarks/fix.py") == []


def test_block_timer_scoped_to_benchmarks_dir():
    assert _lint(_TIMER_BAD, "block-timer", rel="src/repro/core/x.py") == []
    assert _lint(_TIMER_BAD, "block-timer", rel="tests/test_x.py") == []


def test_block_timer_pragma_suppresses():
    src = _TIMER_BAD.replace(
        "    dt = time.perf_counter() - t0",
        "    dt = time.perf_counter() - t0  # repro-lint: disable=block-timer",
    )
    findings = _lint(src, "block-timer", rel="benchmarks/fix.py")
    assert [f.line for f in findings] == [11]


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------


def test_baseline_matches_by_snippet_despite_line_drift():
    findings = _lint(_COMPAT_BAD, "compat-shim")
    assert len(findings) == 2
    entries = [
        {"file": f.file, "pass": f.pass_name, "line": f.line + 40,
         "snippet": f.snippet}
        for f in findings
    ]
    new, old, stale = split_baselined(findings, entries)
    assert new == [] and len(old) == 2 and stale == []


def test_baseline_reports_stale_and_unmatched_entries():
    findings = _lint(_COMPAT_BAD, "compat-shim")
    entries = [
        {"file": findings[0].file, "pass": findings[0].pass_name,
         "snippet": findings[0].snippet},
        {"file": "gone.py", "pass": "compat-shim", "snippet": "import x"},
    ]
    new, old, stale = split_baselined(findings, entries)
    assert len(new) == 1 and len(old) == 1
    assert [e["file"] for e in stale] == ["gone.py"]


# ---------------------------------------------------------------------------
# the live tree and the CLI
# ---------------------------------------------------------------------------


def test_live_tree_has_no_new_findings():
    """`python -m tools.lint src tests benchmarks` stays clean: genuine
    violations get FIXED, intentional ones get a reasoned pragma, and
    only grandfathered debt lives in the committed baseline."""
    findings = run_lint(
        [os.path.join(_ROOT, d) for d in ("src", "tests", "benchmarks")],
        root=_ROOT,
    )
    baseline = load_baseline(
        os.path.join(_ROOT, "tools", "lint", "baseline.json")
    )
    new, _old, stale = split_baselined(findings, baseline)
    assert [f.format() for f in new] == []
    assert stale == []


def test_cli_exit_codes_and_json(tmp_path, capsys):
    from tools.lint.__main__ import main

    bad = tmp_path / "bad.py"
    bad.write_text(_COMPAT_BAD)
    assert main([str(bad), "--no-baseline"]) == 1
    assert "RL003" in capsys.readouterr().out

    assert main([str(bad), "--no-baseline", "--json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert [e["code"] for e in payload] == ["RL003", "RL003"]

    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    assert main([str(good)]) == 0
    assert main(["--list-passes"]) == 0


def test_cli_rejects_unknown_pass_selection(capsys):
    from tools.lint.__main__ import main

    assert main(["--select", "no-such-pass"]) == 2
    assert "unknown pass" in capsys.readouterr().err


def test_check_docs_wrapper_delegates_to_choice_set():
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    try:
        import check_docs
    finally:
        sys.path.pop(0)
    assert check_docs.check() == []
    assert check_docs.code_choices() == choice_set.code_choices(_ROOT)
    assert set(check_docs.documented_choices(check_docs.DOCS.read_text())) == (
        set(check_docs.code_choices())
    )
