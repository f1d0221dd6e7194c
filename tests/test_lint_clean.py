"""fabriclint self-gate (ISSUE 3 tentpole).

Two halves:

1. The GATE: the linter runs over the whole fabric_tpu tree and must
   report zero unsuppressed violations — so a future PR that hashes
   outside the CSP seam, swallows an exception on the validation path,
   or inverts a lock order fails tier-1 here, not in review.  Every
   allowlist entry must carry a reason and match live code (unused
   entries are violations, so the allowlist only shrinks).

2. Per-rule unit tests: each rule fires on a crafted violation AND
   stays quiet on conforming code, pragmas suppress with a reason and
   are themselves checked (reason-less / unknown-rule / unused pragmas
   are meta violations), and string-embedded pragma-shaped text is
   ignored (only real comments count).
"""

import json
import subprocess
import sys

from fabric_tpu.devtools.allowlist import ALLOWLIST
from fabric_tpu.devtools.lint import (
    RULES,
    AllowEntry,
    lint_source,
    lint_tree,
)

# crafted snippets lint as if they lived at these repo-relative paths
LEDGER = "fabric_tpu/ledger/example.py"
PEER = "fabric_tpu/peer/example.py"
CSP = "fabric_tpu/csp/example.py"
GOSSIP = "fabric_tpu/gossip/example.py"  # outside exc/det scopes


def _rules(violations, suppressed=False):
    return sorted(
        v.rule for v in violations if v.suppressed == suppressed
    )


# -- the gate ----------------------------------------------------------------


def test_full_tree_is_clean():
    report = lint_tree()
    assert report.files > 150  # fabric_tpu + tests + scripts
    pretty = "\n".join(str(v) for v in report.unsuppressed)
    assert not report.unsuppressed, f"fabriclint violations:\n{pretty}"
    assert report.summary()["clean"] is True
    # advisory findings may exist, but only from relaxed-profile scopes
    assert all(
        v.path.startswith(("tests/", "scripts/")) for v in report.warnings
    )


def test_every_allowlist_entry_has_a_reviewed_reason():
    for e in ALLOWLIST:
        assert e.rule in RULES, e
        assert e.path.startswith("fabric_tpu/"), e
        assert len(e.reason.strip()) >= 20, (
            f"allowlist entry for {e.path} needs a real reason, "
            f"not {e.reason!r}"
        )


def test_cli_json_summary_and_exit_codes(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "fabric_tpu.devtools.lint", "--json"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["tool"] == "fabriclint"
    assert summary["clean"] is True
    assert summary["violations"] == 0

    # a deliberately dirty file makes the CLI exit non-zero
    bad = tmp_path / "bad.py"
    bad.write_text("import hashlib\nD = hashlib.sha256(b'x').digest()\n")
    proc = subprocess.run(
        [sys.executable, "-m", "fabric_tpu.devtools.lint", "--json",
         "--root", str(tmp_path), "bad.py"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 1
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["clean"] is False
    assert summary["by_rule"] == {"csp-seam": 1}


# -- csp-seam ----------------------------------------------------------------


def test_csp_seam_fires_outside_the_seam():
    src = "import hashlib\nH = hashlib.sha256(b'x').digest()\n"
    assert _rules(lint_source(src, PEER)) == ["csp-seam"]
    # from-import counts too
    src = "from hashlib import sha256\n"
    assert _rules(lint_source(src, LEDGER)) == ["csp-seam"]


def test_csp_seam_quiet_inside_seam_and_through_it():
    src = "import hashlib\nH = hashlib.sha256(b'x').digest()\n"
    assert lint_source(src, CSP) == []
    assert lint_source(src, "fabric_tpu/common/hashing.py") == []
    routed = (
        "from fabric_tpu.common.hashing import sha256\n"
        "H = sha256(b'x')\n"
    )
    assert lint_source(routed, PEER) == []


# -- exception-discipline ----------------------------------------------------


def test_exception_discipline_fires_on_silent_swallow():
    src = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
    )
    assert _rules(lint_source(src, PEER)) == ["exception-discipline"]
    bare = src.replace("except Exception:", "except:")
    assert _rules(lint_source(bare, LEDGER)) == ["exception-discipline"]
    trivial_return = src.replace("pass", "return None")
    assert _rules(lint_source(trivial_return, PEER)) == [
        "exception-discipline"
    ]


def test_exception_discipline_quiet_when_structured():
    logged = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as exc:\n"
        "        log.warning('boom: %s', exc)\n"
    )
    assert lint_source(logged, PEER) == []
    reraise = logged.replace("log.warning('boom: %s', exc)", "raise")
    assert lint_source(reraise, PEER) == []
    sentinel = logged.replace(
        "log.warning('boom: %s', exc)", "return ERR_UNKNOWN_SKI"
    )
    assert lint_source(sentinel, PEER) == []
    narrow = logged.replace("Exception as exc", "ValueError")
    assert lint_source(narrow, PEER) == []
    # out of scope: gossip may use its own error style
    swallow = logged.replace("log.warning('boom: %s', exc)", "pass")
    assert lint_source(swallow, GOSSIP) == []


# -- determinism -------------------------------------------------------------


def test_determinism_fires_on_consensus_paths():
    assert _rules(
        lint_source("import time\nT = time.time()\n",
                    "fabric_tpu/protoutil/example.py")
    ) == ["determinism"]
    assert _rules(
        lint_source("from time import time\nT = time()\n", LEDGER)
    ) == ["determinism"]
    assert _rules(
        lint_source("import random\nX = random.random()\n", PEER)
    ) == ["determinism"]
    assert _rules(
        lint_source("import json\nB = json.dumps({'a': 1})\n", LEDGER)
    ) == ["determinism"]
    # qualified and from-import spellings must not slip past the gate
    assert _rules(
        lint_source("import datetime\nN = datetime.datetime.now()\n",
                    LEDGER)
    ) == ["determinism"]
    assert _rules(
        lint_source("from datetime import datetime as dt\nN = dt.now()\n",
                    PEER)
    ) == ["determinism"]
    assert _rules(
        lint_source("from random import shuffle\nshuffle([1])\n", PEER)
    ) == ["determinism"]


def test_determinism_quiet_on_conforming_code():
    ok = (
        "import json, random, time, datetime\n"
        "B = json.dumps({'a': 1}, sort_keys=True)\n"
        "R = random.Random(7)\n"
        "from random import Random\n"
        "R2 = Random(11)\n"
        "T = time.monotonic()\n"
        "P = time.perf_counter()\n"
        "TZ = datetime.timezone.utc\n"
        "D = datetime.datetime(2020, 1, 1)\n"
    )
    assert lint_source(ok, LEDGER) == []
    # gossip's anti-entropy jitter is outside the consensus scopes
    assert lint_source("import time\nT = time.time()\n", GOSSIP) == []


# -- lock-discipline ---------------------------------------------------------


def test_lock_discipline_fires_on_bare_acquire():
    src = (
        "def f(lock):\n"
        "    lock.acquire()\n"
        "    work()\n"
        "    lock.release()\n"
    )
    assert _rules(lint_source(src, LEDGER)) == ["lock-discipline"]


def test_lock_discipline_quiet_with_try_finally_or_enter():
    # the canonical safe idiom: acquire OUTSIDE the try, immediately
    # followed by a try whose finally releases (a failed acquire never
    # reaches the finally) — quiet
    src = (
        "def f(lock):\n"
        "    lock.acquire()\n"
        "    try:\n"
        "        work()\n"
        "    finally:\n"
        "        lock.release()\n"
    )
    assert lint_source(src, LEDGER) == []
    # acquire inside the try body is also accepted (release is in a
    # finally either way)
    src = (
        "def f(lock):\n"
        "    try:\n"
        "        lock.acquire()\n"
        "        work()\n"
        "    finally:\n"
        "        lock.release()\n"
    )
    assert lint_source(src, LEDGER) == []
    enter = (
        "class L:\n"
        "    def __enter__(self):\n"
        "        self._lock.acquire()\n"
        "        return self\n"
    )
    assert lint_source(enter, LEDGER) == []


def test_lock_discipline_fires_on_with_order_inversion():
    src = (
        "def f(self):\n"
        "    with self._lock:\n"
        "        with self.commit_lock:\n"
        "            pass\n"
    )
    assert _rules(lint_source(src, LEDGER)) == ["lock-discipline"]
    ok = src.replace("self._lock", "X").replace("self.commit_lock", "Y")
    canonical = (
        "def f(self):\n"
        "    with self.commit_lock:\n"
        "        with self._lock:\n"
        "            pass\n"
    )
    assert lint_source(canonical, LEDGER) == []


def test_lock_discipline_fires_on_blocking_io_under_commit_lock():
    src = (
        "import os\n"
        "def f(self, fd):\n"
        "    with self.commit_lock:\n"
        "        os.fsync(fd)\n"
    )
    assert _rules(lint_source(src, LEDGER)) == ["lock-discipline"]
    # ...including transitively through a same-class helper
    helper = (
        "import os\n"
        "class Ledger:\n"
        "    def _flush(self):\n"
        "        os.fsync(self.fd)\n"
        "    def commit(self):\n"
        "        with self.commit_lock:\n"
        "            self._flush()\n"
    )
    assert _rules(lint_source(helper, LEDGER)) == ["lock-discipline"]
    outside = (
        "import os\n"
        "def f(self, fd):\n"
        "    with self._lock:\n"
        "        pass\n"
        "    os.fsync(fd)\n"
    )
    assert lint_source(outside, LEDGER) == []


# -- jax-hygiene -------------------------------------------------------------


def test_jax_hygiene_fires_on_per_item_host_sync():
    src = (
        "def f(xs):\n"
        "    for x in xs:\n"
        "        x.block_until_ready()\n"
    )
    assert _rules(lint_source(src, "fabric_tpu/csp/tpu/example.py")) == [
        "jax-hygiene"
    ]
    batched = (
        "def f(out):\n"
        "    out.block_until_ready()\n"
    )
    assert lint_source(batched, "fabric_tpu/csp/tpu/example.py") == []


# -- suppression machinery ---------------------------------------------------


def test_pragma_suppresses_with_reason():
    src = (
        "import hashlib\n"
        "# fabriclint: allow[csp-seam] reviewed: legacy fingerprint\n"
        "H = hashlib.sha256(b'x').digest()\n"
    )
    vs = lint_source(src, PEER)
    assert _rules(vs) == []  # nothing unsuppressed
    assert _rules(vs, suppressed=True) == ["csp-seam"]
    assert all("legacy fingerprint" in v.suppression
               for v in vs if v.suppressed)


def test_pragma_reaches_through_wrapped_comment_blocks():
    # pragma two comment lines above the flagged line (wrapped reason)
    above = (
        "import hashlib\n"
        "# fabriclint: allow[csp-seam] reviewed: a reason that wraps\n"
        "# onto a second comment line before the code\n"
        "H = hashlib.sha256(b'x').digest()\n"
    )
    assert _rules(lint_source(above, PEER)) == []
    # pragma inside the handler body of a flagged `except` opener
    below = (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        # fabriclint: allow[exception-discipline] reviewed ok\n"
        "        pass\n"
    )
    assert _rules(lint_source(below, PEER)) == []


def test_pragma_does_not_leak_to_the_statement_above():
    # a pragma written for the NEXT statement must not also grant the
    # statement ABOVE it — each suppression covers exactly one reviewed
    # site, so the audit surface never widens by adjacency
    src = (
        "import hashlib\n"
        "A = hashlib.sha256(b'a').digest()\n"
        "# fabriclint: allow[csp-seam] reviewed: only B\n"
        "B = hashlib.sha256(b'b').digest()\n"
    )
    vs = lint_source(src, PEER)
    assert [v.line for v in vs if not v.suppressed] == [2]
    assert [v.line for v in vs if v.suppressed] == [4]


def test_pragma_without_reason_is_a_violation():
    src = (
        "import hashlib\n"
        "# fabriclint: allow[csp-seam]\n"
        "H = hashlib.sha256(b'x').digest()\n"
    )
    assert "pragma" in _rules(lint_source(src, PEER))


def test_unused_and_unknown_pragmas_are_violations():
    unused = "# fabriclint: allow[csp-seam] nothing here to suppress\nX = 1\n"
    assert _rules(lint_source(unused, PEER)) == ["pragma"]
    unknown = (
        "# fabriclint: allow[no-such-rule] typo'd rule name\nX = 1\n"
    )
    rules = _rules(lint_source(unknown, PEER))
    assert rules.count("pragma") == 2  # unknown rule AND unused


def test_pragma_shaped_text_in_strings_is_ignored():
    src = (
        'DOC = "*# fabriclint: allow[csp-seam] example in docs*"\n'
        "import hashlib\n"
        "H = hashlib.sha256(b'x').digest()\n"
    )
    # the string pragma neither suppresses nor registers as unused
    assert _rules(lint_source(src, PEER)) == ["csp-seam"]


def test_allowlist_entry_suppresses_and_unused_entry_flags():
    src = "import time\nT = time.time()\n"
    entry = AllowEntry(
        rule="determinism", path=LEDGER, match="time.time()",
        reason="test entry",
    )
    used = set()
    vs = lint_source(src, LEDGER, allowlist=[entry], used_entries=used)
    assert _rules(vs) == []
    assert used == {0}
    # an entry matching nothing is reported by lint_tree as a violation
    report = lint_tree(allowlist=list(ALLOWLIST) + [AllowEntry(
        rule="determinism", path="fabric_tpu/peer/nope.py",
        match="never-matches", reason="dead entry",
    )])
    dead = [v for v in report.unsuppressed if v.rule == "allowlist"]
    assert len(dead) == 1 and "never-matches" in dead[0].message



# -- taint (unit; the fixture corpus in test_lint_fixtures.py covers the
# cross-function and clean-twin cases) ---------------------------------------


def test_taint_fires_at_the_sink_not_the_source():
    src = (
        "import time\n"
        "from fabric_tpu.protos.common import common_pb2\n"
        "def f():\n"
        "    t = time.time()\n"
        "    hdr = common_pb2.BlockHeader(number=int(t))\n"
    )
    vs = [v for v in lint_source(src, "fabric_tpu/orderer/x.py")
          if v.rule == "taint" and not v.suppressed]
    assert [v.line for v in vs] == [5]  # the constructor, not line 4


def test_taint_ignores_monotonic_and_seeded_random():
    src = (
        "import time, random\n"
        "from fabric_tpu.protos.common import common_pb2\n"
        "def f(rng: random.Random):\n"
        "    t = time.monotonic()\n"
        "    r = random.Random(7)\n"
        "    hdr = common_pb2.BlockHeader(number=int(t))\n"
        "    return hdr.SerializeToString()\n"
    )
    assert lint_source(src, "fabric_tpu/orderer/x.py") == []


def test_taint_follows_fstrings():
    src = (
        "import time\n"
        "from fabric_tpu.protos.common import common_pb2\n"
        "def f():\n"
        "    label = f'at-{time.time()}'\n"
        "    return common_pb2.ChannelHeader(channel_id=label)\n"
    )
    vs = [v for v in lint_source(src, "fabric_tpu/orderer/x.py")
          if v.rule == "taint"]
    assert [v.line for v in vs] == [5]


# -- profiles ----------------------------------------------------------------


def test_relaxed_profile_disables_determinism_and_advisories_seam():
    # tests/ fabricate timestamps by design: determinism/taint off
    src = "import time\nT = time.time()\n"
    assert lint_source(src, "tests/test_example.py") == []
    # ...and hashing expectations directly is advisory, not an error
    hsrc = "import hashlib\nH = hashlib.sha256(b'x').digest()\n"
    vs = lint_source(hsrc, "tests/test_example.py")
    assert [v.severity for v in vs] == ["warning"]
    assert [v.rule for v in vs] == ["csp-seam"]
    # thread-hygiene stays at error even under the relaxed profile
    tsrc = (
        "import threading\n"
        "t = threading.Thread(target=print, daemon=True)\n"
    )
    vs = lint_source(tsrc, "scripts/example.py")
    assert [(v.rule, v.severity) for v in vs] == [
        ("thread-hygiene", "error")
    ]


# -- baseline ratchet --------------------------------------------------------


def test_baseline_ratchet_tolerates_exactly_the_budget(tmp_path):
    from fabric_tpu.devtools.lint import apply_baseline, lint_sources

    dirty = (
        "import threading\n"
        "a = threading.Thread(target=print, daemon=True)\n"
        "b = threading.Thread(target=print, daemon=True)\n"
    )
    report = lint_sources({"fabric_tpu/gossip/x.py": dirty})
    assert report.summary()["by_rule"] == {"thread-hygiene": 2}
    assert apply_baseline(report, {"thread-hygiene": 2})["ok"]
    under = apply_baseline(report, {"thread-hygiene": 1})
    assert not under["ok"] and under["over_budget"] == {"thread-hygiene": 1}
    # a budget looser than reality is itself a failure: the ratchet
    # only tightens, so stale carve-outs die with the violations
    stale = apply_baseline(report, {"thread-hygiene": 3})
    assert not stale["ok"] and stale["stale_budget"] == {"thread-hygiene": 3}


def test_baseline_cli_roundtrip(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import threading\n"
        "t = threading.Thread(target=print, daemon=True)\n"
    )
    base = tmp_path / "baseline.json"
    # write the baseline from the dirty state...
    proc = subprocess.run(
        [sys.executable, "-m", "fabric_tpu.devtools.lint", "--json",
         "--root", str(tmp_path), "--write-baseline", str(base), "bad.py"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert json.loads(base.read_text()) == {"thread-hygiene": 1}
    # ...under which the same tree passes (ratcheted, not clean)
    proc = subprocess.run(
        [sys.executable, "-m", "fabric_tpu.devtools.lint", "--json",
         "--root", str(tmp_path), "--baseline", str(base), "bad.py"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["baseline"]["ok"] is True
    assert summary["baseline"]["ratcheted"] == 1
    # fixing the tree makes the stale budget fail until it is deleted
    bad.write_text("x = 1\n")
    proc = subprocess.run(
        [sys.executable, "-m", "fabric_tpu.devtools.lint", "--json",
         "--root", str(tmp_path), "--baseline", str(base), "bad.py"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 1
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["baseline"]["stale_budget"] == {"thread-hygiene": 1}


def test_hash_seam_rejects_non_sha256_backend():
    # the seam feeds consensus bytes: a backend that is not literal
    # SHA-256 must be refused at install time, not fork the peer later
    import hashlib

    from fabric_tpu.common import hashing

    class Bad:
        def hash(self, b):
            return hashlib.sha1(b).digest()

        def hash_batch(self, bs):
            return [hashlib.sha1(b).digest() for b in bs]

    class Good:
        def hash(self, b):
            return hashlib.sha256(b).digest()

        def hash_batch(self, bs):
            return [hashlib.sha256(b).digest() for b in bs]

    try:
        import pytest

        with pytest.raises(ValueError, match="byte-identical"):
            hashing.set_hash_backend(Bad())
        hashing.set_hash_backend(Good())
        assert hashing.sha256(b"x") == hashlib.sha256(b"x").digest()
    finally:
        hashing.set_hash_backend(None)


def test_rejected_backend_is_not_installed_as_default():
    # a provider the seam probe refuses must not be left as the process
    # default — get_default() users would hash through the rejected
    # backend while the seam stays on hashlib (split-brain digests)
    import hashlib
    import importlib.util

    import pytest

    if importlib.util.find_spec("cryptography") is None:
        pytest.skip("csp.factory needs cryptography; minimal host")
    from fabric_tpu.csp import factory

    class Sha1CSP:
        def hash(self, b):
            return hashlib.sha1(b).digest()

        def hash_batch(self, bs):
            return [hashlib.sha1(b).digest() for b in bs]

    before = factory._default
    with pytest.raises(ValueError, match="byte-identical"):
        factory._install_default(Sha1CSP())
    assert factory._default is before


def test_racecheck_is_enforced_at_error_with_no_baseline():
    """ISSUE 7 acceptance: racecheck is a first-class rule, on at error
    severity in the strict profile, and the tree gate above runs with
    no baseline file — so any unsuppressed racecheck finding fails
    tier-1."""
    from fabric_tpu.devtools.lint import RELAXED_PROFILE, STRICT_PROFILE

    assert "racecheck" in RULES
    assert "racecheck" not in STRICT_PROFILE.disabled
    assert "racecheck" not in STRICT_PROFILE.advisory
    assert "racecheck" in RELAXED_PROFILE.disabled
    import glob
    import os

    from fabric_tpu.devtools.lint import repo_root

    assert not glob.glob(os.path.join(repo_root(), "*baseline*.json")), (
        "the tree must stay clean with NO baseline ratchet file"
    )


# -- dataflow cache (ISSUE 7 satellite) --------------------------------------


def _report_json(report) -> str:
    """Everything observable about a lint run, as canonical JSON —
    cache hits must be indistinguishable from cold runs.  Since v4 the
    observable surface includes the lock-order graph (and the HB facts
    folded into the guard map), so the identity pin covers them too."""
    summary = {k: v for k, v in report.summary().items() if k != "cache"}
    return json.dumps({
        "violations": [v.to_dict() for v in report.violations],
        "summary": summary,
        "summaries": report.function_summaries(),
        "guards": report.guard_map(),
        "lockgraph": report.lock_graph(),
        # v5: the chaos-coverage faultmap and the CFG facts riding the
        # function summaries are cached artifacts too
        "faultmap": report.faultmap(),
        # v6: the three surface-conformance artifacts are cached too —
        # a cache hit must serve them byte-identical to the cold run
        "rpcmap": report.rpcmap(),
        "knobs": report.knobmap(),
        "metricmap": report.metricmap(),
    }, sort_keys=True)


def _write_cache_tree(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "mod.py").write_text(
        "import threading\n"
        "def go():\n"
        "    t = threading.Thread(target=print, daemon=True)\n"
        "    t.start()\n"
        "    t.join()\n"  # lifecycle-quiet: only thread-hygiene fires
    )
    (pkg / "helper.py").write_text(
        "def double(x):\n"
        "    return 2 * x\n"
    )
    # a nested named-lock acquisition so the cached lock-order graph is
    # non-empty — the identity pin must cover real lockgraph content
    (pkg / "locks.py").write_text(
        "from fabric_tpu.devtools.lockwatch import named_lock\n"
        "class P:\n"
        "    def __init__(self):\n"
        "        self._a = named_lock('cachefix.a')\n"
        "        self._b = named_lock('cachefix.b')\n"
        "    def go(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
    )
    # a branchy function so the cached summaries carry a real CFG-facts
    # block (v5) — the identity pin must cover it
    (pkg / "branchy.py").write_text(
        "def walk(items):\n"
        "    total = 0\n"
        "    for it in items:\n"
        "        if it:\n"
        "            total += 1\n"
        "    return total\n"
    )


def test_dataflow_cache_hit_matches_cold_run_exactly(tmp_path):
    from fabric_tpu.devtools.lint import lint_tree

    _write_cache_tree(tmp_path)
    cold = lint_tree(root=str(tmp_path), targets=("pkg",))
    assert cold.cache_state == "miss"
    assert cold.summary()["by_rule"] == {"thread-hygiene": 1}
    # the cold summaries carry real CFG facts for the identity pin
    assert any(
        s.get("cfg", {}).get("back_edges") for s in cold.function_summaries()
    )
    hit = lint_tree(root=str(tmp_path), targets=("pkg",))
    assert hit.cache_state == "hit"
    assert hit.project is None  # served without re-analysis
    assert _report_json(hit) == _report_json(cold)
    # the lockgraph served from cache is the real graph, not a stub
    assert hit.lock_graph()["edges"]["cachefix.a"]["cachefix.b"]
    # the escape hatch bypasses the cache entirely
    off = lint_tree(root=str(tmp_path), targets=("pkg",), cache=False)
    assert off.cache_state == "off"
    assert _report_json(off) == _report_json(cold)


def test_dataflow_cache_invalidates_on_any_file_edit(tmp_path):
    from fabric_tpu.devtools.lint import lint_tree

    _write_cache_tree(tmp_path)
    first = lint_tree(root=str(tmp_path), targets=("pkg",))
    assert first.cache_state == "miss"
    # editing ONE file must invalidate (content-hash keyed)
    (tmp_path / "pkg" / "helper.py").write_text(
        "def double(x):\n"
        "    return x + x\n"
    )
    second = lint_tree(root=str(tmp_path), targets=("pkg",))
    assert second.cache_state == "miss"
    # unchanged tree -> hit again
    third = lint_tree(root=str(tmp_path), targets=("pkg",))
    assert third.cache_state == "hit"


def test_ci_wrapper_guards_out_writes_artifact(tmp_path):
    """scripts/lint.py --guards-out PATH (ISSUE 7 satellite): the
    inferred guarded-by map lands as a JSON artifact next to the
    result line, declared entries included, so reviewers can diff
    guard inference across PRs."""
    import os

    from fabric_tpu.devtools.lint import repo_root

    root = repo_root()
    out_path = tmp_path / "guards.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "lint.py"),
         "--guards-out", str(out_path)],
        capture_output=True, text=True, cwd=root,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["experiment"] == "fabriclint"
    assert result["guards"]["path"] == str(out_path)
    guards = json.loads(out_path.read_text())
    assert len(guards) == result["guards"]["fields"] > 20
    active = guards["fabric_tpu.ledger.kvledger.KVLedger._active_group"]
    assert active["guard"] == "kvledger.commit_lock"
    assert active["source"] == "declared"
    assert active["sites"] > 0
    # majority inference is represented too
    assert any(g["source"] == "inferred" for g in guards.values())


def test_v4_rules_enforced_at_error_with_no_baseline():
    """ISSUE 13 acceptance: lock-order and thread-lifecycle are
    first-class rules, on at error severity in the strict profile, off
    under the relaxed profile like racecheck, and the tree gate runs
    with no baseline file."""
    from fabric_tpu.devtools.lint import RELAXED_PROFILE, STRICT_PROFILE

    for rule in ("lock-order", "thread-lifecycle"):
        assert rule in RULES
        assert rule not in STRICT_PROFILE.disabled
        assert rule not in STRICT_PROFILE.advisory
        assert rule in RELAXED_PROFILE.disabled
    import glob
    import os

    from fabric_tpu.devtools.lint import repo_root

    assert not glob.glob(os.path.join(repo_root(), "*baseline*.json")), (
        "the tree must stay clean with NO baseline ratchet file"
    )


def test_static_lock_graph_is_cycle_free_and_covers_commit_path():
    """The whole-tree acquisition-order graph has no cycles (the gate
    would fail otherwise — this pins the property by name) and contains
    the canonical commit-path ordering the runtime watchdog enforces:
    commit_lock before the snapshot manager/idle locks."""
    from fabric_tpu.devtools.lint import _lock_order_cycles

    report = lint_tree()
    graph = report.lock_graph()
    assert list(_lock_order_cycles(graph)) == []
    commit_succ = graph["edges"]["kvledger.commit_lock"]
    assert "snapshot.manager" in commit_succ
    assert "snapshot.idle" in commit_succ
    # every recorded site is a production site (tests/scripts excluded)
    for _src, dsts in graph["edges"].items():
        for _dst, sites in dsts.items():
            for rel, _line in sites:
                assert not rel.startswith(("tests/", "scripts/")), rel


def test_hb_edges_prove_production_sites_safe():
    """ISSUE 13 acceptance pin: accesses that v3 could only cover with
    a guards.py declaration (or leave in the no-guard/UNKNOWN hole) are
    now positively proven by happens-before edges.

    * ``SnapshotManager._inflight`` is guards.py-DECLARED, and the
      background-export write is additionally HB-proven (``hb_safe``
      rides the declared entry).
    * ``RaftChain._probe_inflight`` (consensus loop vs eviction
      confirm) and ``RPCServer._thread`` (start/join lifecycle) carry
      NO lock anywhere — v4 resolves them as ``hb-publish``: every
      access publication-ordered, no guard needed, racecheck can still
      fire if a future edit adds an unordered access."""
    guards = lint_tree().guard_map()
    inflight = guards["fabric_tpu.ledger.snapshot.SnapshotManager._inflight"]
    assert inflight["source"] == "declared"
    assert inflight.get("hb_safe", 0) >= 1
    for field in (
        "fabric_tpu.orderer.raft.chain.RaftChain._probe_inflight",
        "fabric_tpu.comm.rpc.RPCServer._thread",
    ):
        g = guards[field]
        assert g["source"] == "hb-publish"
        assert g["guard"] is None
        assert g["hb_safe"] == g["sites"] > 0


def test_ci_wrapper_lockgraph_out_writes_artifact(tmp_path):
    """scripts/lint.py --lockgraph-out PATH (ISSUE 13 satellite): the
    static acquisition-order graph lands as a JSON artifact next to the
    result line, in the exact shape the runtime-⊆-static cross-check
    consumes."""
    import os

    from fabric_tpu.devtools.lint import repo_root

    root = repo_root()
    out_path = tmp_path / "lockgraph.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "lint.py"),
         "--lockgraph-out", str(out_path)],
        capture_output=True, text=True, cwd=root,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["experiment"] == "fabriclint"
    assert result["lockgraph"]["path"] == str(out_path)
    graph = json.loads(out_path.read_text())
    assert result["lockgraph"]["roles"] == len(graph["roles"])
    assert result["lockgraph"]["edges"] == sum(
        len(d) for d in graph["edges"].values()
    ) > 10
    sites = graph["edges"]["kvledger.commit_lock"]["snapshot.manager"]
    assert all(
        isinstance(rel, str) and isinstance(line, int)
        for rel, line in sites
    )


def test_ci_wrapper_summaries_out_writes_artifact(tmp_path):
    """scripts/lint.py --summaries-out PATH (ISSUE 6 satellite): the
    per-function dataflow summaries land as a JSON-lines artifact next
    to the bench-style result line."""
    import os

    from fabric_tpu.devtools.lint import repo_root

    root = repo_root()
    out_path = tmp_path / "summaries.jsonl"
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "lint.py"),
         "--summaries-out", str(out_path)],
        capture_output=True, text=True, cwd=root,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["experiment"] == "fabriclint"
    assert result["summaries"]["path"] == str(out_path)
    lines = out_path.read_text().strip().splitlines()
    assert len(lines) == result["summaries"]["functions"] > 100
    sample = json.loads(lines[0])
    assert "function" in sample and "file" in sample


# -- v5 "flowcheck": CFG facts, hb-publish floor, chaos-coverage -------------


def test_v5_chaos_coverage_enforced_at_error_in_both_profiles():
    """ISSUE 18 acceptance: chaos-coverage is a first-class rule, on at
    error severity in BOTH profiles (a test plan is coverage, so tests
    must lint it), and the tree gate still runs with no baseline."""
    from fabric_tpu.devtools.lint import RELAXED_PROFILE, STRICT_PROFILE

    assert "chaos-coverage" in RULES
    for prof in (STRICT_PROFILE, RELAXED_PROFILE):
        assert "chaos-coverage" not in prof.disabled
        assert "chaos-coverage" not in prof.advisory
    import glob
    import os

    from fabric_tpu.devtools.lint import repo_root

    assert not glob.glob(os.path.join(repo_root(), "*baseline*.json")), (
        "the tree must stay clean with NO baseline ratchet file"
    )


def test_hb_publish_count_does_not_decrease_vs_v4():
    """ISSUE 18 acceptance: the CFG-ordered happens-before pass must
    convert conservative silences into proofs, never lose them — the
    v4 guard map carried 171 hb-publish resolutions; v5 holds the
    floor (and production sites gained flow-sensitive CFG facts)."""
    report = lint_tree()
    guards = report.guard_map()
    hb = [g for g in guards.values() if g["source"] == "hb-publish"]
    assert len(hb) >= 171
    # per-function CFG facts are live on the production tree: loops
    # produce back edges, branches produce multi-block functions
    summaries = report.function_summaries()
    cfgs = [s["cfg"] for s in summaries if "cfg" in s]
    assert len(cfgs) > 200
    assert any(c["back_edges"] for c in cfgs)
    # no production function uses a bare acquire/release pair (all
    # critical sections are `with`-scoped), so flow_locks stays empty
    # tree-wide — the explicit-pair half of the flow lockset is pinned
    # by the fix_flow_branchlock / fix_flow_earlyret fixtures
    assert not any(c.get("flow_locks") for c in cfgs)


def test_faultmap_matches_pinned_registry_and_is_deterministic():
    """ISSUE 18 acceptance: the tree's chaos-coverage cross-check is
    green — every statically enumerated seam is armable (exact pin,
    prefix wildcard, or pinned campaign-registry entry) — and the
    pinned registry never names a seam the static scan cannot see
    (registry ⊆ faultmap, the same containment direction tier-1 pins
    for runtime-lockgraph ⊆ static)."""
    from fabric_tpu.devtools.lint import load_faultmap_registry

    report = lint_tree()
    fm = report.faultmap()
    assert not [v for v in report.unsuppressed
                if v.rule == "chaos-coverage"]
    seam_names = {s["name"] for s in fm["seams"]}
    assert len(seam_names) > 30
    assert not fm["dynamic"], "every production seam name is a literal"
    registry = load_faultmap_registry()
    assert len(registry) > 30
    for name, ent in registry.items():
        assert name in seam_names, (
            f"pinned registry names unknown seam {name!r} — stale "
            "export; refresh with scripts/chaos.py --export-registry"
        )
        kinds = {s["kind"] for s in fm["seams"] if s["name"] == name}
        assert set(ent["kinds"]) <= kinds, name
    # the faultmap artifact is byte-deterministic across runs
    a = json.dumps(fm, sort_keys=True)
    b = json.dumps(lint_tree(cache=False).faultmap(), sort_keys=True)
    assert a == b


def test_ci_wrapper_faultmap_out_and_warm_cache_budget(tmp_path):
    """scripts/lint.py --faultmap-out PATH + --budget-s S (ISSUE 18
    satellite): the faultmap lands as a JSON artifact beside the
    result line, and a warm-cache full-tree pass fits the 1.5 s budget
    the CI gate asserts — the CFG pass cannot quietly double tier-1
    setup cost."""
    import os

    from fabric_tpu.devtools.lint import repo_root

    root = repo_root()
    out_path = tmp_path / "faultmap.json"
    # first run warms the cache (no budget: it may be a cold miss)
    warm = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "lint.py")],
        capture_output=True, text=True, cwd=root,
    )
    assert warm.returncode == 0, warm.stdout + warm.stderr
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "lint.py"),
         "--faultmap-out", str(out_path), "--budget-s", "1.5"],
        capture_output=True, text=True, cwd=root,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["experiment"] == "fabriclint"
    assert result["cache"] == "hit"
    assert result["budget"] == {"budget_s": 1.5, "ok": True}
    assert result["faultmap"]["path"] == str(out_path)
    fm = json.loads(out_path.read_text())
    assert result["faultmap"]["seams"] == len(fm["seams"]) > 50
    assert result["faultmap"]["plans"] == len(fm["plans"]) > 50
    sample = fm["seams"][0]
    assert {"name", "kind", "module", "line"} <= set(sample)


# -- v6 "surfcheck": rpc/knob/metrics conformance ----------------------------


def test_v6_surface_trio_enforced_at_error_with_no_baseline():
    """ISSUE 19 acceptance: rpc-conformance, knob-conformance, and
    metrics-conformance bring the rule count to 14, all on at error
    severity in the strict profile with no baseline — and off under
    the relaxed profile (they anchor at production sites only)."""
    from fabric_tpu.devtools.lint import RELAXED_PROFILE, STRICT_PROFILE

    assert len(RULES) == 14
    for rule in ("rpc-conformance", "knob-conformance",
                 "metrics-conformance"):
        assert rule in RULES
        assert rule not in STRICT_PROFILE.disabled
        assert rule not in STRICT_PROFILE.advisory
        assert rule in RELAXED_PROFILE.disabled
    import glob
    import os

    from fabric_tpu.devtools.lint import repo_root

    assert not glob.glob(os.path.join(repo_root(), "*baseline*.json")), (
        "the tree must stay clean with NO baseline ratchet file"
    )


def test_v6_tree_artifacts_cover_the_real_surfaces():
    """The whole-tree artifacts are non-degenerate: every gateway/
    deliver/participation method is mapped with both register and call
    sites, every registry knob has a read site, and the metric planes
    carry the production series netscope consumes."""
    from fabric_tpu.devtools import knob_registry

    report = lint_tree()
    rpc = report.rpcmap()["methods"]
    assert len(rpc) >= 25
    for method in ("ab.Broadcast", "deliver.DeliverFiltered",
                   "participation.List", "endorser.ProcessProposal",
                   "net.TraceDump"):
        assert rpc[method]["registers"], method
        assert rpc[method]["calls"], method
    knobs = report.knobmap()
    assert set(knobs["registry"]) == set(knob_registry.KNOBS)
    read_names = {r["name"] for r in knobs["reads"]}
    assert read_names == set(knob_registry.KNOBS)
    assert knobs["dynamic"] == []
    mm = report.metricmap()
    assert all(p["registered"] for p in mm["producers"])
    assert len(mm["exposed"]) >= 60
    consumed = {c["name"] for c in mm["consumers"]}
    assert "ledger_height" in consumed
    assert consumed <= set(mm["exposed"])


def test_nothing_names_a_knob_the_registry_lacks():
    """Fifteen knobs since PR 51 (the two of the withdrawn sharded
    store and the WAL checkpoint threshold went).  A name that left the
    registry is left nowhere: not in the README (its generated table
    is the lint rule's to hold, the prose around it is not), not in a
    source, script or sample config."""
    import os
    import re

    from fabric_tpu.devtools import knob_registry
    from fabric_tpu.devtools.lint import repo_root

    assert len(knob_registry.KNOBS) == 15
    root = repo_root()
    paths = [os.path.join(root, "README.md")]
    for top in ("fabric_tpu", "scripts", "sampleconfig"):
        for dirpath, _dirs, files in os.walk(os.path.join(root, top)):
            paths.extend(
                os.path.join(dirpath, f) for f in files
                if f.endswith((".py", ".cc", ".sh", ".yaml", ".json"))
            )
    named = {}
    for path in paths:
        with open(path, encoding="utf-8") as f:
            text = f.read()
        # a bare prefix ("FABRIC_TPU_*") names no knob
        for name in re.findall(r"FABRIC_TPU_[A-Z0-9][A-Z0-9_]*", text):
            named.setdefault(name, os.path.relpath(path, root))
    strangers = {n: rel for n, rel in named.items()
                 if n not in knob_registry.KNOBS}
    assert strangers == {}
    assert set(named) == set(knob_registry.KNOBS)


def test_ci_wrapper_v6_artifacts_byte_identical_cold_vs_hit(tmp_path):
    """scripts/lint.py --rpcmap-out/--knobs-out/--metricmap-out (ISSUE
    19 satellite): all three artifacts land beside the result line,
    and a --no-cache cold pass writes byte-identical files to a
    warm-cache hit — determinism of the cached artifact plane."""
    import os

    from fabric_tpu.devtools.lint import repo_root

    root = repo_root()

    def run(tag, *extra):
        paths = {
            kind: str(tmp_path / f"{kind}_{tag}.json")
            for kind in ("rpcmap", "knobs", "metricmap")
        }
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "scripts", "lint.py"),
             "--rpcmap-out", paths["rpcmap"],
             "--knobs-out", paths["knobs"],
             "--metricmap-out", paths["metricmap"], *extra],
            capture_output=True, text=True, cwd=root,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        result = json.loads(proc.stdout.strip().splitlines()[-1])
        return result, paths

    cold, cold_paths = run("cold", "--no-cache")
    assert cold["cache"] == "off"
    hit, hit_paths = run("hit")
    assert hit["cache"] == "hit"
    for kind in ("rpcmap", "knobs", "metricmap"):
        a = open(cold_paths[kind], "rb").read()
        b = open(hit_paths[kind], "rb").read()
        assert a == b, f"{kind} artifact differs cold vs hit"
    assert hit["rpcmap"]["methods"] >= 25
    assert hit["knobs"]["knobs"] == 15
    assert hit["knobs"]["reads"] >= 15
    assert hit["metricmap"]["producers"] >= 40
    assert hit["metricmap"]["exposed"] >= 60
