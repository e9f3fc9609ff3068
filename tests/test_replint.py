"""Fixture tests for the replint invariant linter (DESIGN.md §13).

Each rule gets a fires-on-violation / silent-on-fix fixture pair, plus
CLI contract tests (rule selection, pragma allowlisting, JSON schema,
exit codes) and a repo-wide sweep asserting the tree stays clean.
The final section pins the two determinism bugs the linter's first
sweep found in the shipped transports.
"""
import json
import os
import textwrap

import pytest

from repro.devtools.replint import lint_file, lint_paths, rule_names
from repro.devtools.replint.__main__ import main

REPO_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def _lint(tmp_path, rel, source, select=None, design=None):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(source))
    return lint_file(str(p), select=select, design=design)


def _rules(findings):
    return [f.rule for f in findings]


# --------------------------------------------------------------------------
# determinism


def test_determinism_flags_wallclock_and_global_rng(tmp_path):
    fs = _lint(tmp_path, "repro/net/mod.py", """\
        import time
        import random
        import numpy as np

        def f():
            t = time.time()
            x = random.random()
            v = np.random.rand(3)
            k = id(t)
            return t, x, v, k
        """, select=["determinism"])
    assert _rules(fs) == ["determinism"] * 4
    msgs = " | ".join(f.message for f in fs)
    assert "wall-clock" in msgs and "random.random" in msgs
    assert "np.random.rand" in msgs and "id()" in msgs


def test_determinism_unseeded_default_rng(tmp_path):
    fs = _lint(tmp_path, "repro/runtime/mod.py", """\
        from numpy.random import default_rng

        bad = default_rng()
        good = default_rng(42)
        """, select=["determinism"])
    assert len(fs) == 1 and "unseeded" in fs[0].message
    assert fs[0].line == 3


def test_determinism_set_iteration(tmp_path):
    fs = _lint(tmp_path, "repro/net/mod.py", """\
        def f(xs):
            s = set(xs)
            for x in s:
                print(x)
            return [y for y in {1, 2, 3}]
        """, select=["determinism"])
    assert _rules(fs) == ["determinism"] * 2


def test_determinism_sorted_set_iteration_is_clean(tmp_path):
    fs = _lint(tmp_path, "repro/net/mod.py", """\
        def f(xs):
            s = set(xs)
            lo = min(x for x in s)
            return sorted(y for y in s), lo
        """, select=["determinism"])
    assert fs == []


def test_determinism_inherited_set_attr(tmp_path):
    fs = _lint(tmp_path, "repro/runtime/mod.py", """\
        class Base:
            def __init__(self):
                self.alive = set()

        class Sub(Base):
            def drain(self):
                for w in self.alive:
                    print(w)
        """, select=["determinism"])
    assert len(fs) == 1 and "self.alive" in fs[0].message


def test_determinism_scoped_to_net_and_runtime(tmp_path):
    fs = _lint(tmp_path, "repro/bench/mod.py", """\
        import time
        t = time.time()
        """, select=["determinism"])
    assert fs == []


# --------------------------------------------------------------------------
# pool-reset


def test_pool_reset_flags_leaked_state(tmp_path):
    fs = _lint(tmp_path, "mod.py", """\
        class Flow:
            def __init__(self, sim):
                self.sim = sim        # wiring: from a param, not flagged
                self.buf = []
                self.seen = set()

            def reset(self, gen=None):
                self.seen = set()
        """, select=["pool-reset"])
    assert len(fs) == 1
    assert "self.buf" in fs[0].message and "Flow" in fs[0].message


def test_pool_reset_mutator_and_helper_coverage(tmp_path):
    fs = _lint(tmp_path, "mod.py", """\
        class Flow:
            def __init__(self):
                self.buf = []
                self.count = 0

            def reset(self, gen=None):
                self.buf.clear()
                self._rearm()

            def _rearm(self):
                self.count = 0
        """, select=["pool-reset"])
    assert fs == []


def test_pool_reset_ignores_classes_without_protocol(tmp_path):
    fs = _lint(tmp_path, "mod.py", """\
        class NotPooled:
            def __init__(self):
                self.buf = []
        """, select=["pool-reset"])
    assert fs == []


# --------------------------------------------------------------------------
# gen-fence


def test_gen_fence_flags_raw_g_key(tmp_path):
    fs = _lint(tmp_path, "repro/net/mod.py", """\
        def stale(meta, gen):
            return meta["g"] != gen

        def mark(meta, gen):
            meta = {"g": gen}
            return meta
        """, select=["gen-fence"])
    assert _rules(fs) == ["gen-fence"] * 2
    assert all("genfence" in f.message for f in fs)


def test_gen_fence_ignores_fstring_format_specs(tmp_path):
    fs = _lint(tmp_path, "repro/net/mod.py", """\
        def label(x):
            return f"os{x:g}"
        """, select=["gen-fence"])
    assert fs == []


def test_gen_fence_exempts_the_helper_module_itself(tmp_path):
    fs = _lint(tmp_path, "repro/net/genfence.py", """\
        GEN_KEY = "g"
        """, select=["gen-fence"])
    assert fs == []


def test_gen_fence_unguarded_sim_callback(tmp_path):
    fs = _lint(tmp_path, "repro/runtime/mod.py", """\
        class R:
            def arm(self, t):
                def cb():
                    self.count += 1
                    self.apply()
                self.sim.at(t, cb)
        """, select=["gen-fence"])
    assert len(fs) == 1 and "'cb'" in fs[0].message


def test_gen_fence_guarded_and_delegating_callbacks_pass(tmp_path):
    fs = _lint(tmp_path, "repro/runtime/mod.py", """\
        class R:
            def arm(self, t):
                def cb():
                    if self.closed:
                        return
                    self.apply()
                self.sim.at(t, cb)
                self.sim.after(t, lambda: self.tick())

            def launch(self, worker, it):
                def done():
                    if self._flight.pop((worker, it), None) is None:
                        return
                    self.apply()
                self.sim.after(1.0, done)
        """, select=["gen-fence"])
    assert fs == []


# --------------------------------------------------------------------------
# hotpath


def test_hotpath_flags_allocations_in_marked_function(tmp_path):
    fs = _lint(tmp_path, "mod.py", """\
        # replint: hotpath
        def hot(xs):
            ys = [x + 1 for x in xs]
            cb = lambda: None
            return f"{ys}", cb
        """, select=["hotpath"])
    assert _rules(fs) == ["hotpath"] * 3
    msgs = " | ".join(f.message for f in fs)
    assert "comprehension" in msgs and "lambda" in msgs and "f-string" in msgs


def test_hotpath_unmarked_functions_are_ignored(tmp_path):
    fs = _lint(tmp_path, "mod.py", """\
        def cold(xs):
            return [x + 1 for x in xs]
        """, select=["hotpath"])
    assert fs == []


def test_hotpath_tracker_arm_is_exempt(tmp_path):
    fs = _lint(tmp_path, "mod.py", """\
        # replint: hotpath
        def hot(self, v):
            self.total += v
            if self._h_observe is not None:
                self._h_observe(f"v={v}")
            else:
                bad = [v for _ in range(2)]
        """, select=["hotpath"])
    # the else-arm still counts: only the tracker arm itself is exempt
    assert len(fs) == 1 and "comprehension" in fs[0].message


def test_hotpath_flags_profiler_spans_on_any_arm(tmp_path):
    fs = _lint(tmp_path, "mod.py", """\
        import jax
        from jax.profiler import StepTraceAnnotation

        # replint: hotpath
        def hot(self, pkt):
            with jax.profiler.TraceAnnotation("pkt"):
                self.n += 1
            if self._h_observe is not None:
                with StepTraceAnnotation("pkt", step_num=self.n):
                    self._h_observe(self.n)
        """, select=["hotpath"])
    assert _rules(fs) == ["hotpath"] * 2
    assert all("profiler span" in f.message for f in fs)


def test_hotpath_spans_outside_marked_functions_are_fine(tmp_path):
    fs = _lint(tmp_path, "mod.py", """\
        from jax.profiler import TraceAnnotation

        # replint: hotpath
        def hot(self, pkt):
            self.n += 1

        def per_round(self, it):
            with TraceAnnotation("ltp.masks", iteration=it):
                self.hot(it)
        """, select=["hotpath"])
    assert fs == []


# --------------------------------------------------------------------------
# frozen-config


def test_frozen_config_flags_unhashable_fields(tmp_path):
    fs = _lint(tmp_path, "repro/config.py", """\
        import dataclasses
        from typing import List, Tuple

        @dataclasses.dataclass(frozen=True)
        class Cfg:
            racks: List[int]
            sizes: "List[float]"
            shape: Tuple[int, ...] = ()
        """, select=["frozen-config"])
    assert _rules(fs) == ["frozen-config"] * 2
    assert {"racks", "sizes"} == {f.message.split("Cfg.")[1].split()[0]
                                  for f in fs}


def test_frozen_config_only_applies_to_config_py(tmp_path):
    src = """\
        import dataclasses
        from typing import List

        @dataclasses.dataclass(frozen=True)
        class Cfg:
            racks: List[int]
        """
    assert _lint(tmp_path, "repro/other.py", src,
                 select=["frozen-config"]) == []


def test_frozen_config_ignores_unfrozen_dataclasses(tmp_path):
    fs = _lint(tmp_path, "repro/config.py", """\
        import dataclasses
        from typing import List

        @dataclasses.dataclass
        class Mutable:
            racks: List[int]
        """, select=["frozen-config"])
    assert fs == []


# --------------------------------------------------------------------------
# design-ref


def test_design_ref_resolution(tmp_path):
    (tmp_path / "DESIGN.md").write_text("# Design\n\n## §3 Close rule\n")
    fs = _lint(tmp_path, "repro/mod.py", """\
        # the close rule (DESIGN.md §3) applies here
        # but this one is stale: DESIGN.md §99
        """, select=["design-ref"])
    assert len(fs) == 1 and "§99" in fs[0].message


def test_design_ref_explicit_design_path(tmp_path):
    d = tmp_path / "docs.md"
    d.write_text("## §7 Trains\n")
    fs = _lint(tmp_path, "deep/mod.py", "# see DESIGN.md §7 and DESIGN.md §8\n",
               select=["design-ref"], design=str(d))
    assert len(fs) == 1 and "§8" in fs[0].message


def test_design_ref_silent_without_a_design_file(tmp_path):
    fs = _lint(tmp_path, "repro/mod.py", "# cites DESIGN.md §42\n",
               select=["design-ref"])
    assert fs == []


# --------------------------------------------------------------------------
# pragmas and pseudo-rules


def test_pragma_suppresses_trailing_and_own_line(tmp_path):
    fs = _lint(tmp_path, "repro/net/mod.py", """\
        import time

        def f():
            a = time.time()  # replint: ok(determinism)
            # replint: ok(determinism)
            b = time.time()
            c = time.time()
            return a, b, c
        """, select=["determinism"])
    assert len(fs) == 1 and fs[0].line == 7


def test_pragma_hygiene_unknown_rule_and_malformed(tmp_path):
    fs = _lint(tmp_path, "mod.py", """\
        x = 1  # replint: ok(no-such-rule)
        y = 2  # replint: wibble
        z = 3  # replint: ok()
        """)
    assert _rules(fs) == ["pragma"] * 3
    msgs = " | ".join(f.message for f in fs)
    assert "unknown rule" in msgs and "unrecognized pragma" in msgs \
        and "names no rule" in msgs


def test_pragma_unused_reported_only_on_full_runs(tmp_path):
    src = """\
        x = 1  # replint: ok(determinism)
        """
    full = _lint(tmp_path, "a/mod.py", src)
    assert _rules(full) == ["pragma"] and "unused" in full[0].message
    partial = _lint(tmp_path, "b/mod.py", src, select=["pool-reset"])
    assert partial == []


def test_parse_error_is_a_finding_not_a_crash(tmp_path):
    fs = _lint(tmp_path, "mod.py", "def broken(:\n")
    assert _rules(fs) == ["parse"] and "syntax error" in fs[0].message


# --------------------------------------------------------------------------
# CLI contract


@pytest.fixture
def bad_tree(tmp_path):
    p = tmp_path / "repro" / "net" / "mod.py"
    p.parent.mkdir(parents=True)
    p.write_text("import time\nt = time.time()\n")
    return tmp_path


def test_cli_exit_codes(bad_tree, tmp_path, capsys):
    clean = tmp_path / "clean"
    clean.mkdir()
    (clean / "ok.py").write_text("x = 1\n")
    assert main([str(clean)]) == 0
    assert main([str(bad_tree)]) == 1
    assert main([]) == 2
    assert main(["--select", "no-such-rule", str(clean)]) == 2
    out = capsys.readouterr()
    assert "replint: clean" in out.out
    assert "no paths given" in out.err and "unknown rule(s)" in out.err


def test_cli_rule_selection(bad_tree, capsys):
    assert main(["--select", "pool-reset", str(bad_tree)]) == 0
    assert main(["--select", "determinism", str(bad_tree)]) == 1
    out = capsys.readouterr().out
    assert "[determinism]" in out and "determinism: 1" in out


def test_cli_json_schema(bad_tree, capsys):
    assert main(["--json", str(bad_tree)]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert set(doc) == {"findings", "counts", "files_scanned"}
    assert doc["files_scanned"] == 1
    assert doc["counts"] == {"determinism": 1}
    (f,) = doc["findings"]
    assert set(f) == {"rule", "path", "line", "col", "message"}
    assert f["rule"] == "determinism" and f["line"] == 2


def test_cli_list_rules(capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name in ("determinism", "pool-reset", "gen-fence", "hotpath",
                 "frozen-config", "design-ref"):
        assert name in out


def test_rule_registry_is_complete():
    assert rule_names() == ["determinism", "pool-reset", "gen-fence",
                            "hotpath", "frozen-config", "design-ref"]


# --------------------------------------------------------------------------
# the tree itself stays clean


def test_repo_sweep_is_clean():
    findings, n_files = lint_paths([REPO_SRC])
    assert findings == [], "\n".join(f.render() for f in findings)
    assert n_files > 50


# --------------------------------------------------------------------------
# regressions pinned by the linter's first sweep (real determinism bugs)


def test_tcp_prune_inflight_fills_retx_in_seq_order():
    """_prune_inflight used to iterate the inflight *set* directly, so the
    retransmit queue refilled in hash order — same-seed replays could
    schedule retransmissions differently across set histories."""
    from repro.net.senders import RenoSender
    from repro.net.simcore import Pipe, Sim

    sim = Sim()
    pipe = Pipe(sim, rate_bps=1e9, delay=0.001)
    snd = RenoSender(sim, pipe, deliver=lambda p: None, n_packets=100)
    seqs = [37, 5, 91, 12, 60, 3]
    snd.inflight = set(seqs)
    for s in seqs:
        snd.sent_time[s] = -1e9       # far older than any RTO cutoff
    snd.retx.clear()
    snd._prune_inflight()
    assert list(snd.retx) == sorted(seqs)
    assert snd.inflight == set()


def test_ps_gather_stop_resends_in_flow_order():
    """The post-close stop-resend loop used to iterate a set of flow ids;
    stop packets now go out in sorted flow order so the event sequence
    is identical across replays."""
    from repro.net.ltp_receiver import PSGatherReceiver
    from repro.net.simcore import Packet, Sim

    sim = Sim()
    stops = []
    rx = PSGatherReceiver(sim, flows=[3, 1, 2], lt_threshold=1.0,
                          deadline=2.0, pct_threshold=0.8,
                          send_stop=stops.append)
    rx.closed = True
    items = [(Packet(f, 0, 100, kind="data"), 0.0) for f in (3, 1, 3, 2)]
    rx.on_data_train(items)
    assert stops == [1, 2, 3]
    assert rx.n_stop_resends == 3
