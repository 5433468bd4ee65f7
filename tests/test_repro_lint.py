"""Tests for tools/lint (repro-lint): per-rule positive/negative fixtures,
suppression comments, baseline round-trip + drift, and the meta-test that
the live tree lints clean against the committed baseline.

Fixture files are written under tmp_path with directory names that match
each rule's path scoping (kernels/, core/, serving/, src/).
"""

import textwrap
from pathlib import Path

from tools.lint import lint_paths
from tools.lint.rules import all_rules

REPO_ROOT = Path(__file__).resolve().parents[1]


def run_lint(tmp_path, files, **kw):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    kw.setdefault("baseline_path", None)
    return lint_paths(["."], root=tmp_path, **kw)


def codes(res):
    return sorted(f.code for f in res.new)


class TestFramework:
    def test_rule_discovery_finds_all_four_families(self):
        by_family = {r.code[:4] for r in all_rules()}
        assert {"RPL1", "RPL2", "RPL3", "RPL4"} <= by_family
        assert len(all_rules()) >= 12

    def test_legacy_template_marker_quarantines_file(self, tmp_path):
        bad = """
            # repro-lint: legacy-template — scaffold kept for tests
            import numpy as np
            import jax

            @jax.jit
            def f(x):
                return np.asarray(x)
        """
        res = run_lint(tmp_path, {"kernels/old.py": bad})
        assert res.new == [] and res.n_legacy == 1

    def test_syntax_error_reports_exit_2(self, tmp_path):
        res = run_lint(tmp_path, {"kernels/broken.py": "def f(:\n"})
        assert res.errors and res.exit_code == 2


class TestRPL101HostSync:
    POS = """
        import jax
        import numpy as np
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            y = np.asarray(x)      # host round-trip
            z = float(jnp.sum(x))  # concretizes a traced value
            return x.item()        # device sync
    """

    def test_positive(self, tmp_path):
        res = run_lint(tmp_path, {"kernels/k.py": self.POS})
        assert codes(res).count("RPL101") == 3

    def test_negative_outside_jit(self, tmp_path):
        src = """
            import numpy as np
            import jax.numpy as jnp

            def host_fn(x):
                y = np.asarray(x)
                return float(jnp.sum(y))
        """
        assert run_lint(tmp_path, {"kernels/k.py": src}).new == []

    def test_negative_outside_device_modules(self, tmp_path):
        res = run_lint(tmp_path, {"scripts/tool.py": self.POS})
        assert "RPL101" not in codes(res)

    def test_static_metadata_and_static_args_allowed(self, tmp_path):
        src = """
            import functools
            import jax
            import numpy as np

            @functools.partial(jax.jit, static_argnames=("min_pts",))
            def f(x, min_pts):
                big = np.iinfo(np.int32).max   # trace-time metadata: fine
                k = float(min_pts)             # static arg: fine
                return x * k + big
        """
        assert run_lint(tmp_path, {"kernels/k.py": src}).new == []

    def test_transitive_callee_is_jit_reachable(self, tmp_path):
        src = """
            import jax
            import numpy as np

            def helper(c):
                return int(np.ceil(np.log2(max(c, 2)))) + 1

            @jax.jit
            def f(x):
                n = helper(x.shape[0])
                return x * n
        """
        res = run_lint(tmp_path, {"core/h_jax.py": src})
        assert codes(res).count("RPL101") == 2  # np.ceil and np.log2

    def test_wrapped_jit_assignment_is_reachable(self, tmp_path):
        src = """
            import jax

            def f(x):
                return x.item()

            g = jax.jit(f)
        """
        res = run_lint(tmp_path, {"kernels/k.py": src})
        assert "RPL101" in codes(res)


class TestRPL102Pow2Buckets:
    def test_positive_and_negative(self, tmp_path):
        src = """
            def _pad_rows(a, n):
                return a

            def use(a, b):
                x = _pad_rows(a, 48)   # not a power of two
                y = _pad_rows(b, 64)   # fine
                return x, y
        """
        res = run_lint(tmp_path, {"kernels/k.py": src})
        assert codes(res) == ["RPL102"]


class TestRPL103MutableDefaults:
    def test_positive_and_negative(self, tmp_path):
        src = """
            import jax

            @jax.jit
            def f(x, opts=[]):
                return x

            @jax.jit
            def g(x, opts=()):
                return x

            def host(x, opts=[]):
                return x
        """
        res = run_lint(tmp_path, {"kernels/k.py": src})
        assert codes(res) == ["RPL103"]


class TestRPL201DeviceF64:
    def test_positive_in_jit_negative_on_host(self, tmp_path):
        src = """
            import jax
            import numpy as np
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                return jnp.asarray(x, jnp.float64)

            def host_oracle_side(x):
                return np.asarray(x, dtype=np.float64)  # §2 mandates this
        """
        res = run_lint(tmp_path, {"core/bubble_flat.py": src})
        assert codes(res) == ["RPL201"]


class TestRPL202OracleF32:
    def test_positive_in_oracle_negative_elsewhere(self, tmp_path):
        src = """
            import numpy as np

            def core_distances(x):
                return x.astype(np.float32)
        """
        res = run_lint(tmp_path, {"core/hdbscan.py": src})
        assert codes(res) == ["RPL202"]
        res2 = run_lint(tmp_path / "neg", {"core/summarizer.py": src})
        assert "RPL202" not in codes(res2)


class TestRPL203UncenteredHandoff:
    def test_entry_point_without_centering_fires(self, tmp_path):
        src = """
            import numpy as np

            def _build_entry(snap):
                rep = snap.bubble_rep.astype(np.float32)
                return rep
        """
        res = run_lint(tmp_path, {"serving/query.py": src})
        assert codes(res) == ["RPL203"]

    def test_entry_point_with_centering_is_clean(self, tmp_path):
        src = """
            import numpy as np

            def _build_entry(snap):
                rep = (snap.bubble_rep - snap.center[None, :]).astype(np.float32)
                return rep
        """
        assert run_lint(tmp_path, {"serving/query.py": src}).new == []

    def test_non_entry_point_is_not_checked(self, tmp_path):
        src = """
            import numpy as np

            def some_other_fn(snap):
                return snap.bubble_rep.astype(np.float32)
        """
        assert run_lint(tmp_path, {"serving/query.py": src}).new == []


class TestRPL301UnannotatedShared:
    POS = """
        class Engine:
            def __init__(self):
                self.counts = {}

            def bump(self, k):
                self.counts[k] = self.counts.get(k, 0) + 1
    """

    def test_positive(self, tmp_path):
        res = run_lint(tmp_path, {"serving/eng.py": self.POS})
        assert codes(res) == ["RPL301"]

    def test_annotation_silences(self, tmp_path):
        for ann in (
            "# guarded-by: _lock", "# owner: ingest thread",
            "# unsynchronized: best-effort counter",
        ):
            src = self.POS.replace("self.counts = {}", f"self.counts = {{}}  {ann}")
            res = run_lint(tmp_path, {"serving/eng.py": src})
            assert "RPL301" not in codes(res), ann

    def test_read_only_attr_not_flagged(self, tmp_path):
        src = """
            class Engine:
                def __init__(self, kw):
                    self.kw = dict(kw)

                def get(self, k):
                    return self.kw[k]
        """
        assert run_lint(tmp_path, {"serving/eng.py": src}).new == []


class TestRPL302GuardedAccess:
    def test_unlocked_access_fires(self, tmp_path):
        src = """
            import threading

            class C:
                def __init__(self):
                    self._m = {}  # guarded-by: _lock
                    self._lock = threading.Lock()

                def bad(self, k):
                    return self._m.get(k)

                def good(self, k):
                    with self._lock:
                        return self._m.get(k)
        """
        res = run_lint(tmp_path, {"serving/c.py": src})
        assert codes(res) == ["RPL302"]
        assert res.new[0].line and "bad" in res.new[0].message

    def test_holds_annotation_silences(self, tmp_path):
        src = """
            import threading

            class C:
                def __init__(self):
                    self._m = {}  # guarded-by: _lock
                    self._lock = threading.Lock()

                def inner(self, k):  # holds: _lock
                    return self._m.get(k)
        """
        assert run_lint(tmp_path, {"serving/c.py": src}).new == []


class TestRPL303LockOrder:
    INIT_OK = "# lock-order: A._la -> B._lb\n"
    INIT_BAD = "# lock-order: B._lb -> A._la\n"
    MOD = """
        import threading

        class B:
            def __init__(self):
                self._lb = threading.Lock()
                self.n = 0  # guarded-by: _lb

            def bump(self):
                with self._lb:
                    self.n += 1

        class A:
            def __init__(self):
                self._la = threading.Lock()
                self.b = B()
                self.total = 0  # guarded-by: _la

            def outer(self):
                with self._la:
                    self.total += 1
                    self.b.bump()
    """

    def test_declared_order_respected(self, tmp_path):
        res = run_lint(
            tmp_path, {"serving/__init__.py": self.INIT_OK, "serving/mod.py": self.MOD}
        )
        assert "RPL303" not in codes(res)

    def test_inverted_order_fires(self, tmp_path):
        res = run_lint(
            tmp_path, {"serving/__init__.py": self.INIT_BAD, "serving/mod.py": self.MOD}
        )
        assert "RPL303" in codes(res)

    def test_may_acquire_annotation_feeds_the_check(self, tmp_path):
        mod = """
            import threading

            class A:
                def __init__(self):
                    self._la = threading.Lock()
                    self.total = 0  # guarded-by: _la

                def outer(self, eng):
                    with self._la:
                        self.total += 1
                        eng.refresh()  # may-acquire: B._lb
        """
        res = run_lint(
            tmp_path, {"serving/__init__.py": self.INIT_BAD, "serving/a.py": mod}
        )
        assert "RPL303" in codes(res)
        res2 = run_lint(
            tmp_path, {"serving/__init__.py": self.INIT_OK, "serving/a.py": mod}
        )
        assert "RPL303" not in codes(res2)


class TestRPL401BlockSpecPow2:
    def test_positive_and_negative(self, tmp_path):
        src = """
            from jax.experimental import pallas as pl

            def kernels(bn):
                bad = pl.BlockSpec((48, 64), lambda i: (i, 0))
                ok = pl.BlockSpec((bn, 128), lambda i: (i, 0))
                return bad, ok
        """
        res = run_lint(tmp_path, {"src/repro/kernels/k.py": src})
        assert codes(res) == ["RPL401"]


class TestRPL402DenseMaterialization:
    def test_dense_call_outside_ref_fires(self, tmp_path):
        src = """
            from repro.kernels import ref as _ref

            def assign_all(x, reps):
                return _ref.pairwise_sqdist(x, reps).argmin(axis=1)
        """
        res = run_lint(tmp_path, {"src/repro/serving/fastpath.py": src})
        assert codes(res) == ["RPL402"]

    def test_ref_and_documented_dense_are_exempt(self, tmp_path):
        src = """
            import jax.numpy as jnp

            def pairwise_sqdist(x, y):
                return jnp.zeros((4, 4))
        """
        assert run_lint(tmp_path, {"src/repro/kernels/ref.py": src}).new == []
        doc = """
            import jax.numpy as jnp

            def bubble_mutual_reachability(rep, L):
                return jnp.zeros((L, L))
        """
        assert run_lint(tmp_path, {"src/repro/kernels/ops2.py": doc}).new == []

    def test_square_same_name_alloc_fires(self, tmp_path):
        src = """
            import jax.numpy as jnp

            def build(L):
                return jnp.full((L, L), 1e30)
        """
        res = run_lint(tmp_path, {"src/repro/kernels/k.py": src})
        assert codes(res) == ["RPL402"]


class TestRPL403GridInts:
    def test_positive_and_negative(self, tmp_path):
        src = """
            from jax.experimental import pallas as pl

            def launch(kernel, Lp, bn):
                bad = pl.pallas_call(kernel, grid=(4.5,))
                ok = pl.pallas_call(kernel, grid=(Lp // bn,))
                return bad, ok
        """
        res = run_lint(tmp_path, {"src/repro/kernels/k.py": src})
        assert codes(res) == ["RPL403"]


class TestSuppression:
    BAD_LINE = "    y = np.asarray(x)\n"

    def _src(self, line):
        return (
            "import jax\nimport numpy as np\n\n"
            "@jax.jit\ndef f(x):\n" + line + "    return x\n"
        )

    def test_same_line_disable(self, tmp_path):
        src = self._src("    y = np.asarray(x)  # repro-lint: disable=RPL101\n")
        assert run_lint(tmp_path, {"kernels/k.py": src}).new == []

    def test_comment_above_disable(self, tmp_path):
        src = self._src(
            "    # repro-lint: disable=RPL101\n    y = np.asarray(x)\n"
        )
        assert run_lint(tmp_path, {"kernels/k.py": src}).new == []

    def test_star_disables_everything(self, tmp_path):
        src = self._src("    y = np.asarray(x)  # repro-lint: disable=*\n")
        assert run_lint(tmp_path, {"kernels/k.py": src}).new == []

    def test_wrong_code_still_fires(self, tmp_path):
        src = self._src("    y = np.asarray(x)  # repro-lint: disable=RPL402\n")
        assert codes(run_lint(tmp_path, {"kernels/k.py": src})) == ["RPL101"]


class TestBaseline:
    SRC = """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.asarray(x)
    """

    def test_round_trip(self, tmp_path):
        (tmp_path / "kernels").mkdir(parents=True)
        (tmp_path / "kernels/k.py").write_text(textwrap.dedent(self.SRC))
        bl = tmp_path / "baseline.txt"
        res = lint_paths(["."], root=tmp_path, baseline_path=bl, update_baseline=True)
        assert len(res.grandfathered) == 1 and bl.exists()
        res2 = lint_paths(["."], root=tmp_path, baseline_path=bl)
        assert res2.new == [] and res2.stale == [] and res2.exit_code == 0

    def test_fixed_finding_reports_stale_entry(self, tmp_path):
        (tmp_path / "kernels").mkdir(parents=True)
        f = tmp_path / "kernels/k.py"
        f.write_text(textwrap.dedent(self.SRC))
        bl = tmp_path / "baseline.txt"
        lint_paths(["."], root=tmp_path, baseline_path=bl, update_baseline=True)
        f.write_text(textwrap.dedent(self.SRC).replace(
            "np.asarray(x)", "x"))
        res = lint_paths(["."], root=tmp_path, baseline_path=bl)
        assert res.exit_code == 2 and (res.stale or res.errors)

    def test_line_drift_is_detected(self, tmp_path):
        (tmp_path / "kernels").mkdir(parents=True)
        f = tmp_path / "kernels/k.py"
        f.write_text(textwrap.dedent(self.SRC))
        bl = tmp_path / "baseline.txt"
        lint_paths(["."], root=tmp_path, baseline_path=bl, update_baseline=True)
        f.write_text("# a new comment shifts every line\n" + textwrap.dedent(self.SRC))
        res = lint_paths(["."], root=tmp_path, baseline_path=bl)
        assert res.exit_code == 2 and res.errors  # drifted anchor line
        res2 = lint_paths(["."], root=tmp_path, baseline_path=bl, update_baseline=True)
        assert len(res2.grandfathered) == 1
        res3 = lint_paths(["."], root=tmp_path, baseline_path=bl)
        assert res3.exit_code == 0


class TestLiveTree:
    def test_repo_lints_clean_against_committed_baseline(self):
        res = lint_paths(
            ["src", "tests", "scripts"], root=REPO_ROOT,
            baseline_path=REPO_ROOT / "tools/lint/baseline.txt",
        )
        assert res.errors == [], res.errors
        assert res.stale == [], [e.render() for e in res.stale]
        assert res.new == [], [f.render() for f in res.new]

    def test_serving_has_zero_unannotated_shared_attrs(self):
        res = lint_paths(
            ["src/repro/serving"], root=REPO_ROOT, baseline_path=None,
            select={"RPL301"},
        )
        assert res.new == [], [f.render() for f in res.new]
