"""The solver service (``--serve``, ISSUE 16): admission control,
request isolation, caches, coalescing, and the self-healing loop.

The acceptance contract:
  * a second identical request pays ZERO ingest and ZERO compile --
    asserted via the ``acg_serve_cache_*`` families AND the untouched
    ``acg_compiles_total`` counter;
  * a coalesced batch answers each member BITWISE equal to serving it
    singly (the batched-classic column-identity, re-pinned here);
  * the bounded queue sheds with a typed 429, an expired request is
    answered with a typed 504 -- never a hang;
  * SLO error-budget burn drives degrade-before-refuse: past
    ``degrade_burn`` requests are served on the cheap profile and
    marked ``degraded``; past ``shed_burn`` they are refused typed;
  * a crashed daemon relaunches under the supervisor and WARM-RESTORES
    its operator cache from the persisted serve state;
  * the chaos campaign against the LIVE daemon (schedule 1 forced
    crash-mid-request) ends serving with zero wrong-answer-green.
"""

import argparse
import contextlib
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from acg_tpu import metrics, observatory
from acg_tpu import supervisor as sup_mod
from acg_tpu.cli import synthesize_host_matrix
from acg_tpu.serve import (COALESCE_WINDOW_SECS, RequestRefused,
                           SCHEMA, STATE_SCHEMA, ServeConfig,
                           ServeDaemon, _Request, _serve_validate,
                           config_from_args, serve_chaos_schedule)

MATRIX = "gen:poisson2d:12"
_CSR = synthesize_host_matrix(MATRIX).to_csr()
N = int(_CSR.shape[0])

ENV = {"JAX_PLATFORMS": "cpu", "JAX_ENABLE_X64": "1",
       "PYTHONPATH": os.path.dirname(os.path.dirname(
           os.path.abspath(__file__)))}


def _counter(name: str) -> float:
    """Sum every sample of a counter family in the exposition (labeled
    or not) -- tests assert DELTAS, the registry is process-global."""
    total = 0.0
    for line in metrics.expose().splitlines():
        if line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        if head == name or head.startswith(name + "{"):
            total += float(val)
    return total


@contextlib.contextmanager
def _daemon(**kw):
    kw.setdefault("port", 0)
    kw.setdefault("default_timeout", 60.0)
    d = ServeDaemon(ServeConfig(**kw))
    d.start()
    try:
        yield d
    finally:
        d.stop()
        observatory._clear_slo()


def _doc(**kw):
    doc = {"matrix": MATRIX, "rtol": 1e-8, "maxits": 300}
    doc.update(kw)
    return doc


def _await_slow_lead(d, timeout: float = 5.0) -> None:
    """Block until the worker is serving a request submitted from
    another thread: the worker stamps its ``queue-wait`` stage as it
    hands the batch to the solver.  An empty queue alone may only mean
    the request is not submitted yet."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline and not any(
            "queue-wait" in r["stages"]
            for r in d.reqlog.snapshot()["inflight"]):
        time.sleep(0.01)


def _true_rel(x, b) -> float:
    r = b - _CSR @ np.asarray(x, dtype=np.float64)
    return float(np.linalg.norm(r) / np.linalg.norm(b))


# -- validation & refusal matrix ------------------------------------------

def _serve_args(**kw):
    ns = argparse.Namespace(
        A=MATRIX, soak=0, resume=None, b=None, x0=None, output=None,
        explain=False, bench=False, nrhs=0, block_cg=False,
        fault_inject=None, manufactured_solution=False,
        distributed_read=False, output_comm_matrix=False,
        profile_ops=None, ckpt=None, serve_port=0,
        serve_queue_depth=16, serve_coalesce=8, serve_deadline=60.0,
        nparts=0, comm="xla", dtype="f64", serve_faults=False)
    for k, v in kw.items():
        setattr(ns, k, v)
    return ns


def test_serve_validate_refusal_matrix():
    _serve_validate(_serve_args())  # the clean profile passes
    for kw, frag in [
            ({"soak": 5}, "--soak"),
            ({"resume": "snap"}, "--resume"),
            ({"b": "b.npy"}, "b/x0"),
            ({"output": "x.npy"}, "-o/--output"),
            ({"explain": True}, "--explain"),
            ({"bench": True}, "--bench"),
            ({"nrhs": 4}, "--nrhs"),
            ({"block_cg": True}, "--nrhs"),
            ({"fault_inject": "spmv:nan@3"}, "--fault-inject"),
            ({"manufactured_solution": True}, "--manufactured"),
            ({"plan": "p.json"}, "--plan"),
            ({"A": "matrix.mtx"}, "gen:")]:
        with pytest.raises(SystemExit, match=frag):
            _serve_validate(_serve_args(**kw))


def test_config_from_args_state_suffix():
    cfg = config_from_args(_serve_args(ckpt="/tmp/ck"))
    assert cfg.state_path == "/tmp/ck.serve.json"
    assert cfg.preload == MATRIX
    assert config_from_args(_serve_args()).state_path is None


def test_request_validation_refusals():
    cfg = ServeConfig()
    for doc, kind, status in [
            ({}, "invalid-request", 400),
            ({"matrix": "file.mtx"}, "invalid-request", 400),
            ({"matrix": MATRIX, "dtype": "f16"}, "invalid-request",
             400),
            ({"matrix": MATRIX, "algorithm": "sstep:zz"},
             "invalid-request", 400),
            ({"matrix": MATRIX, "maxits": 0}, "invalid-request", 400),
            ({"matrix": MATRIX, "timeout": -1}, "invalid-request",
             400),
            ({"matrix": MATRIX, "rtol": "soon"}, "invalid-request",
             400),
            ({"matrix": MATRIX, "b": ["x", "y"]}, "invalid-request",
             400),
            ({"matrix": MATRIX, "fault": "crash"}, "faults-disabled",
             403)]:
        with pytest.raises(RequestRefused) as ei:
            _Request(doc, cfg)
        assert ei.value.kind == kind
        assert ei.value.status == status
    # faults pass once the daemon was armed for them
    armed = ServeConfig(allow_faults=True)
    assert _Request({"matrix": MATRIX, "fault": "crash"},
                    armed).fault == "crash"


def test_coalesce_key_compatibility():
    cfg = ServeConfig(allow_faults=True)
    a = _Request(_doc(b_seed=1), cfg)
    b = _Request(_doc(b_seed=2), cfg)
    assert a.coalesce_key(cfg) is not None
    assert a.coalesce_key(cfg) == b.coalesce_key(cfg)
    # every incompatibility opts out of the bitwise-equal merge
    for doc in [_doc(coalesce=False), _doc(fault="slow:0.1"),
                _doc(precond="jacobi"),
                _doc(algorithm="pipelined:2")]:
        assert _Request(doc, cfg).coalesce_key(cfg) is None
    assert _Request(_doc(rtol=1e-6),
                    cfg).coalesce_key(cfg) != a.coalesce_key(cfg)
    assert _Request(_doc(algorithm="classic"),
                    cfg).coalesce_key(cfg) == a.coalesce_key(cfg)


# -- caches: steady state is zero ingest, zero compile --------------------

def test_repeat_request_zero_ingest_zero_compile():
    with _daemon() as d:
        c0 = _counter("acg_compiles_total")
        s1, b1 = d.submit(_doc(b_seed=7))
        assert s1 == 200 and b1["ok"] and b1["converged"]
        assert b1["cache"] == {"operator": "miss", "program": "miss"}
        c1 = _counter("acg_compiles_total")
        assert c1 > c0  # the miss absorbed AND counted its compile
        hits0 = _counter("acg_serve_cache_hits_total")
        s2, b2 = d.submit(_doc(b_seed=8))
        assert s2 == 200 and b2["ok"]
        assert b2["cache"] == {"operator": "hit", "program": "hit"}
        # THE acceptance assertion: a repeated request pays zero
        # ingest and zero compile
        assert _counter("acg_compiles_total") == c1
        assert _counter("acg_serve_cache_hits_total") >= hits0 + 2
        b = np.random.default_rng(8).standard_normal(N)
        assert _true_rel(b2["x"], b) <= 1e-8
        assert d.requests_served == 2
        doc = d.status_doc()
        assert doc["schema"] == SCHEMA and doc["serving"]
        assert doc["operator_cache"]["entries"] == 1
        assert doc["program_cache"]["entries"] == 1


def test_program_cache_keyed_by_shape():
    with _daemon() as d:
        d.submit(_doc(b_seed=1))
        # a different recurrence is a different program: operator hit,
        # program miss
        s, body = d.submit(_doc(b_seed=1, algorithm="pipelined:2",
                                coalesce=False))
        assert s == 200
        assert body["cache"] == {"operator": "hit", "program": "miss"}
        assert len(d.programs) == 2


# -- coalescing: bitwise equal to single service --------------------------

def test_coalesced_batch_bitwise_equals_single():
    seeds = [11, 22, 33]
    with _daemon(allow_faults=True, coalesce=4) as d:
        # pin the singles first (fresh program cache, nrhs=1)
        singles = {}
        for s in seeds:
            st, body = d.submit(_doc(b_seed=s, coalesce=False))
            assert st == 200 and body["coalesced"] == 1
            singles[s] = (body["x"], body["iterations"])
        # block the worker with a slow fault request (itself
        # uncoalescible), queue the three compatible followers behind
        # it, and let the drain merge them into ONE batched solve
        results = {}
        threads = [threading.Thread(
            target=lambda: d.submit(_doc(fault="slow:0.6",
                                         b_seed=99)))]
        threads[0].start()
        _await_slow_lead(d)

        def _go(seed):
            results[seed] = d.submit(_doc(b_seed=seed))

        for s in seeds:
            t = threading.Thread(target=_go, args=(s,))
            threads.append(t)
            t.start()
        coal0 = _counter("acg_serve_coalesced_total")
        for t in threads:
            t.join(timeout=120.0)
        for s in seeds:
            st, body = results[s]
            assert st == 200 and body["ok"]
            assert body["coalesced"] == len(seeds)
            # the bitwise pin: same bits, same per-RHS iteration count
            assert body["x"] == singles[s][0]
            assert body["iterations"] == singles[s][1]
        assert _counter("acg_serve_coalesced_total") == \
            coal0 + len(seeds)


# -- admission control: queue, deadline, SLO ladder -----------------------

def test_queue_full_sheds_typed_429():
    with _daemon(allow_faults=True, queue_depth=1) as d:
        d.submit(_doc(b_seed=1))  # warm the caches
        shed0 = _counter("acg_serve_shed_total")
        t = threading.Thread(
            target=lambda: d.submit(_doc(fault="slow:0.8", b_seed=2)))
        t.start()
        _await_slow_lead(d)  # the worker holds the slow lead
        filler = threading.Thread(
            target=lambda: d.submit(_doc(b_seed=3)))
        filler.start()
        deadline = time.monotonic() + 5.0
        while len(d.queue) < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        status, body = d.submit(_doc(b_seed=4))
        assert status == 429
        assert body["error"]["type"] == "shed-queue-full"
        assert body["error"]["retryable"]
        t.join(timeout=60.0)
        filler.join(timeout=60.0)
        assert _counter("acg_serve_shed_total") > shed0


def test_expired_request_answers_typed_504():
    with _daemon(allow_faults=True, queue_depth=4) as d:
        d.submit(_doc(b_seed=1))  # warm the caches
        t = threading.Thread(
            target=lambda: d.submit(_doc(fault="slow:0.8", b_seed=2)))
        t.start()
        _await_slow_lead(d)
        # queued behind 0.8s of service with a 0.2s budget: the worker
        # answers it typed the moment it pops -- never a hang
        status, body = d.submit(_doc(b_seed=3, timeout=0.2))
        assert status == 504
        assert body["error"]["type"] == "deadline-expired"
        assert body["error"]["retryable"]
        t.join(timeout=60.0)


def test_slo_burn_degrades_then_sheds():
    with _daemon(degrade_burn=0.4, shed_burn=0.75) as d:
        d.submit(_doc(b_seed=1))  # warm (and observe nothing: no SLO)
        observatory.install_slo(observatory.parse_slo("iters=1"))
        observatory.slo_observe(iterations=100)  # breach
        observatory.slo_observe(iterations=1)    # ok -> burn 0.5
        deg0 = _counter("acg_serve_degraded_total")
        status, body = d.submit(_doc(b_seed=2,
                                     algorithm="pipelined:2",
                                     coalesce=False))
        assert status == 200 and body["ok"]
        assert body["degraded"] is True
        assert _counter("acg_serve_degraded_total") == deg0 + 1
        b = np.random.default_rng(2).standard_normal(N)
        assert _true_rel(body["x"], b) <= 1e-8  # degraded, not wrong
        # burn past the shed rung -> typed refusal, not service
        observatory.slo_observe(iterations=100)
        observatory.slo_observe(iterations=100)
        status, body = d.submit(_doc(b_seed=3))
        assert status == 503
        assert body["error"]["type"] == "shed-slo-burn"
        assert body["error"]["retryable"]


def test_stopped_daemon_sheds_typed():
    d = ServeDaemon(ServeConfig(port=0))
    d.start()
    d.stop()
    status, body = d.submit(_doc(b_seed=1))
    assert status == 503
    assert body["error"]["type"] == "shed-shutdown"
    observatory._clear_slo()


# -- request isolation ----------------------------------------------------

def test_fault_request_is_isolated_and_retried():
    with _daemon(allow_faults=True, retries=1,
                 retry_backoff=0.01) as d:
        inval0 = _counter("acg_serve_cache_invalidations_total")
        # dot:nan trips the solve; the retry (fault dropped: it
        # modelled a transient) must answer green from a fresh program
        status, body = d.submit(_doc(b_seed=5, fault="dot:nan@2",
                                     coalesce=False))
        assert status == 200 and body["ok"]
        b = np.random.default_rng(5).standard_normal(N)
        assert _true_rel(body["x"], b) <= 1e-8
        # the daemon survived and still serves
        status, body = d.submit(_doc(b_seed=6))
        assert status == 200 and body["ok"]
        assert _counter("acg_serve_cache_invalidations_total") \
            >= inval0


def test_unconverged_request_answers_typed_500():
    with _daemon() as d:
        status, body = d.submit(_doc(b_seed=1, maxits=2))
        assert status == 500 and not body["ok"]
        assert body["error"]["type"] == "NotConvergedError"
        assert body["error"]["retryable"]
        # isolation: the daemon still answers the next request
        status, body = d.submit(_doc(b_seed=1))
        assert status == 200 and body["ok"]


# -- self-healing: state sidecar + warm restore ---------------------------

def test_state_sidecar_and_warm_restore(tmp_path):
    state = str(tmp_path / "serve.json")
    with _daemon(state_path=state) as d:
        st, _ = d.submit(_doc(b_seed=1))
        assert st == 200
    with open(state) as f:
        doc = json.load(f)
    assert doc["schema"] == STATE_SCHEMA
    assert doc["requests_served"] == 1
    assert doc["operators"] == [[MATRIX, "f64", 0]]
    warm0 = _counter("acg_serve_warm_restores_total")
    with _daemon(state_path=state) as d2:
        assert d2.warm_restored == 1
        assert _counter("acg_serve_warm_restores_total") == warm0 + 1
        # the first request of the new incarnation already hits the
        # re-ingested operator (only the program must rebuild)
        st, body = d2.submit(_doc(b_seed=2))
        assert st == 200
        assert body["cache"]["operator"] == "hit"
        assert body["cache"]["program"] == "miss"


def test_unreadable_state_is_cold_start(tmp_path):
    state = str(tmp_path / "serve.json")
    with open(state, "w") as f:
        f.write("{not json")
    with _daemon(state_path=state) as d:
        assert d.warm_restored == 0
        st, body = d.submit(_doc(b_seed=1))
        assert st == 200 and body["cache"]["operator"] == "miss"


# -- the HTTP surface -----------------------------------------------------

def test_http_endpoints_end_to_end():
    import urllib.error
    import urllib.request

    with _daemon() as d:
        base = f"http://127.0.0.1:{d.port}"

        def get(path):
            with urllib.request.urlopen(base + path, timeout=30.0) \
                    as resp:
                return resp.status, resp.read().decode()

        status, body = get("/healthz")
        assert status == 200 and json.loads(body)["ok"]
        status, body = get("/status")
        assert status == 200
        assert json.loads(body)["schema"] == SCHEMA
        status, body = get("/metrics")
        assert status == 200 and "acg_serve_requests_total" in body
        status, body = get("/requests")
        assert status == 200
        reqdoc = json.loads(body)
        assert reqdoc["schema"] == "acg-serve-requests/1"
        assert reqdoc["inflight"] == [] and reqdoc["completed"] == []
        req = urllib.request.Request(
            base + "/solve",
            data=json.dumps(_doc(b_seed=9,
                                 return_x=False)).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120.0) as resp:
            out = json.loads(resp.read().decode())
        assert out["ok"] and out["converged"] and "x" not in out
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/solve", data=b"{not json"), timeout=30.0)
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            get("/nope")
        assert ei.value.code == 404


# -- grow-on-recovery (the supervisor's other ratchet half) ---------------

def test_supervisor_regrow_relaunch_argv_surgery(tmp_path):
    from acg_tpu.observatory import DEGRADED_ENV
    metrics.arm()
    sup = sup_mod.DaemonSupervisor(
        [MATRIX, "--serve", "--nparts", "8"],
        state_path=str(tmp_path / "s.json"), nparts=8, grow_after=3,
        backoff=0.0)
    launches = []
    sup._launch = lambda: launches.append(list(sup.argv))
    # a crash-class death shrinks and marks the fleet degraded
    sup._relaunch(parts=4, reason="crash-injected", grow=False)
    assert sup.cur_parts == 4
    assert sup.report["degraded"] == {"from": 8, "to": 4,
                                      "reason": "crash-injected"}
    assert sup.env[DEGRADED_ENV] == "8:4:crash-injected"
    assert "--nparts" in launches[0] \
        and launches[0][launches[0].index("--nparts") + 1] == "4"
    # healthy for grow_after requests -> deliberate regrow relaunch
    re0 = _counter("acg_recovery_regrows_total")
    sup._relaunch(parts=8, reason="regrow", grow=True)
    assert sup.cur_parts == 8
    assert sup.report["regrows"] == 1
    assert sup.report["degraded"] is None  # back at full width
    assert DEGRADED_ENV not in sup.env
    assert "--resume-repartition" in launches[1]
    assert launches[1][launches[1].index("--nparts") + 1] == "8"
    assert _counter("acg_recovery_regrows_total") == re0 + 1
    assert len(sup.report["relaunches"]) == 1  # regrow is not a death


def test_serve_chaos_schedule_deterministic_and_crashful():
    a = [serve_chaos_schedule(i, 1234, 0) for i in range(8)]
    b = [serve_chaos_schedule(i, 1234, 0) for i in range(8)]
    assert a == b  # seeded: the campaign is replayable
    assert a[1] == {"fault": "crash"}  # schedule 1 is ALWAYS a crash
    for sched in a:
        f = sched.get("fault")
        assert f is None or f == "crash" or f.startswith("slow:") \
            or f.startswith(("spmv:", "dot:"))
    # halo faults only enter the menu when there IS a mesh
    singles = [serve_chaos_schedule(i, 99, 0).get("fault")
               for i in range(40)]
    assert all(f is None or not f.startswith("halo:")
               for f in singles)


# -- the live campaign (subprocess; the t1.yml smoke twin) ----------------

@pytest.mark.slow
def test_crash_relaunch_warm_cache_live(tmp_path):
    """Kill the daemon mid-request via the chaos campaign (schedule 1
    is a forced crash): the supervisor must relaunch it, the relaunch
    must warm-restore, and every response must verify."""
    env = dict(os.environ, **ENV)
    r = subprocess.run(
        [sys.executable, "-m", "acg_tpu.cli", MATRIX, "--comm",
         "none", "--serve", "--serve-faults", "--chaos", "77:2",
         "--ckpt", str(tmp_path / "ck"), "--relaunch-backoff", "0",
         "--max-iterations", "400", "--residual-rtol", "1e-8",
         "--quiet", "--history", str(tmp_path / "history")],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "warm-restored" in r.stderr
    rows = [e["doc"] for e in
            observatory.history_scan(tmp_path / "history")
            if e["doc"].get("schema") == "acg-tpu-chaos-serve/1"]
    assert len(rows) == 2
    verdicts = {row["chaos"]["verdict"] for row in rows}
    assert "crash-relaunched" in verdicts
    assert "WRONG-ANSWER" not in verdicts
    assert "HANG" not in verdicts


# -- decision observatory (--serve --autotune) ----------------------------

def _serve_cal(**over):
    from acg_tpu import commbench as cb
    doc = {"schema": cb.COMMBENCH_SCHEMA, "backend": "cpu", "nparts": 8,
           "collectives": {
               "all_reduce": {"alpha_s": 1e-5, "beta_s_per_byte": 1e-10,
                              "npoints": 3, "r2": None},
               "all_to_all": {"alpha_s": 2e-5,
                              "beta_s_per_byte": 1e-9,
                              "npoints": 3, "r2": None}}}
    doc.update(over)
    doc["calibration_id"] = cb.calibration_id(doc)
    return doc


def test_serve_autotune_plans_and_stamps_provenance():
    """--serve --autotune: the daemon plans on operator-cache miss,
    stamps every response with plan id + decision provenance, surfaces
    the cached decisions under /status plans:, and replans when the
    calibration id changes (the serve satellite of ISSUE 17)."""
    cal = _serve_cal()
    with _daemon(autotune=True, calibration=cal) as d:
        s1, b1 = d.submit(_doc(b_seed=1))
        assert s1 == 200 and b1["ok"]
        assert b1["plan"]["source"] == "planned", b1["plan"]
        assert str(b1["plan"]["id"]).startswith("plan-"), b1["plan"]
        doc = d.status_doc()
        plans = doc["plans"]
        assert plans["autotune"] is True
        assert plans["calibration"] == cal["calibration_id"]
        assert plans["decisions"] and \
            plans["decisions"][0]["plan_id"] == b1["plan"]["id"]
        assert plans["last_misprediction_ratio"] > 0
        # an explicit per-request algorithm overrides the plan: the
        # provenance says so instead of silently re-labelling
        s2, b2 = d.submit(_doc(b_seed=2, algorithm="classic"))
        assert s2 == 200 and b2["ok"]
        assert b2["plan"]["source"] == "flag-forced", b2["plan"]
        # calibration swap -> the next planned request replans
        cal2 = _serve_cal(nparts=8, backend="cpu",
                          note="recalibrated")
        assert cal2["calibration_id"] != cal["calibration_id"]
        d.set_calibration(cal2)
        s3, b3 = d.submit(_doc(b_seed=3))
        assert s3 == 200 and b3["ok"]
        assert b3["plan"]["source"] == "planned", b3["plan"]
        doc2 = d.status_doc()
        assert doc2["plans"]["calibration"] == cal2["calibration_id"]
        assert all(dec["calibration"] == cal2["calibration_id"]
                   for dec in doc2["plans"]["decisions"])


# -- the request observatory (ISSUE 18) -----------------------------------

TRACEPARENT = "00-4bf92f3577b34da6a3ce929d0e0e4736-00f067aa0ba902b7-01"


def test_request_identity_echo_and_access_ledger(tmp_path):
    """Every request resolves to a request_id (client id > traceparent
    trace-id > generated), echoed on the 200 AND the 400 path, and
    --access-log lands exactly one acg-tpu-access/1 row per request
    that scripts/check_access_log.py accepts."""
    ledger = str(tmp_path / "access.jsonl")
    with _daemon(access_log=ledger) as d:
        s1, b1 = d.submit(_doc(b_seed=1, request_id="client-1"))
        assert s1 == 200 and b1["request_id"] == "client-1"
        s2, b2 = d.submit(_doc(b_seed=2, traceparent=TRACEPARENT))
        assert s2 == 200
        assert b2["request_id"] == "4bf92f3577b34da6a3ce929d0e0e4736"
        s3, b3 = d.submit(_doc(b_seed=3))
        assert s3 == 200 and b3["request_id"].startswith("req-")
        # the refusal path carries the identity too
        s4, b4 = d.submit(_doc(maxits=0, request_id="bad-1"))
        assert s4 == 400 and b4["request_id"] == "bad-1"
        assert b4["error"]["type"] == "invalid-request"
        # the response contract is the PR 17 body plus ONE additive
        # field -- the id; nothing else moved
        assert set(b1) == {"ok", "schema", "id", "request_id",
                           "converged", "iterations",
                           "latency_seconds", "cache", "coalesced",
                           "degraded", "plan", "x"}
        # per-stage seconds reached the histogram surface
        expo = metrics.expose()
        assert 'acg_serve_stage_seconds_bucket{stage="solve"' in expo
        assert "acg_serve_inflight" in expo
        doc = d.status_doc()
        assert doc["requests"]["completed"] == 4
        assert doc["requests"]["outcomes"] == {"ok": 3,
                                               "invalid-request": 1}
        assert doc["requests"]["access_log"] == ledger
    with open(ledger) as f:
        rows = [json.loads(line) for line in f]
    assert [r["request_id"] for r in rows] == \
        ["client-1", "4bf92f3577b34da6a3ce929d0e0e4736",
         b3["request_id"], "bad-1"]
    for r in rows[:3]:
        assert r["outcome"] == "ok"
        assert sum(r["stages"].values()) <= r["wall_seconds"] + 5e-3
        for stage in ("admit", "queue-wait", "cache", "solve",
                      "demux", "respond"):
            assert stage in r["stages"], (r["request_id"], stage)
    assert rows[3]["outcome"] == "invalid-request"
    res = subprocess.run(
        [sys.executable, "scripts/check_access_log.py", ledger,
         "--min-rows", "4", "--require-outcome", "ok",
         "--require-outcome", "invalid-request"],
        capture_output=True, text=True, cwd=ENV["PYTHONPATH"])
    assert res.returncode == 0, res.stderr


def test_concurrent_coalesced_requests_trace_one_solve(tmp_path):
    """N parallel POST /solve coalescing into one batch: /requests
    never tears under fire, each member lands its own ledger row, the
    rows share ONE batch block whose per-RHS attribution sums back to
    the batch solve time, and the armed timeline carries a single
    worker solve-batch span linked to ALL member request ids."""
    from acg_tpu import tracing

    ledger = str(tmp_path / "access.jsonl")
    seeds = [11, 22, 33]
    ids = {s: f"member-{s}" for s in seeds}
    try:
        tracing.arm()
        with _daemon(allow_faults=True, coalesce=4,
                     access_log=ledger) as d:
            d.submit(_doc(b_seed=1))  # warm the caches
            # block the worker with an uncoalescible slow lead, queue
            # the members behind it so the drain merges them
            threads = [threading.Thread(
                target=lambda: d.submit(_doc(fault="slow:0.6",
                                             b_seed=99,
                                             request_id="slow-lead")))]
            threads[0].start()
            _await_slow_lead(d)
            results = {}

            def _go(seed):
                results[seed] = d.submit(
                    _doc(b_seed=seed, request_id=ids[seed]))

            for s in seeds:
                t = threading.Thread(target=_go, args=(s,))
                threads.append(t)
                t.start()
            # the non-torn read under fire: in-flight + completed
            # documents are never half-written
            for _ in range(20):
                snap = d.reqlog.snapshot()
                assert snap["schema"] == "acg-serve-requests/1"
                for doc in snap["inflight"] + snap["completed"]:
                    assert doc["request_id"]
                    assert isinstance(doc["stages"], dict)
                time.sleep(0.01)
            for t in threads:
                t.join(timeout=120.0)
            for s in seeds:
                st, body = results[s]
                assert st == 200 and body["ok"]
                assert body["coalesced"] == len(seeds)
                assert body["request_id"] == ids[s]
        spans = tracing.local_payload()["spans"]
    finally:
        tracing.disarm()

    with open(ledger) as f:
        rows = {r["request_id"]: r
                for r in map(json.loads, f)
                if r["request_id"] in ids.values()}
    assert set(rows) == set(ids.values())
    batches = {r["batch"]["id"] for r in rows.values()}
    assert len(batches) == 1  # ONE solve, three attributions
    blk = next(iter(rows.values()))["batch"]
    assert blk["width"] == len(seeds)
    assert sorted(blk["members"]) == sorted(ids.values())
    assert abs(blk["rhs_solve_seconds"] * blk["width"]
               - blk["solve_seconds"]) <= 1e-3
    for r in rows.values():
        assert r["batch"] == blk  # every member links the same block
        assert r["stages"]["queue-wait"] > 0  # they waited on the lead
        assert abs(r["stages"]["solve"]
                   - blk["rhs_solve_seconds"]) <= 1e-3
    # the worker track: one solve-batch span naming every member
    worker = [s for s in spans if s["cat"] == "worker"
              and s["name"].startswith("solve-batch")
              and set((s.get("args") or {}).get("requests", []))
              >= set(ids.values())]
    assert len(worker) == 1
    # and each member's lane carries its own request-scoped spans
    for rid in ids.values():
        mine = [s for s in spans if s["cat"] == "request"
                and (s.get("args") or {}).get("request") == rid]
        assert {s["name"] for s in mine} >= {"queue-wait", "solve",
                                             "demux"}


def test_serve_without_autotune_has_no_plan_section():
    """Disarmed (no --autotune) the daemon neither plans nor stamps:
    responses carry no plan id and the decision is flag-forced --
    byte-compatible with the PR 16 response contract plus the one
    additive plan field."""
    with _daemon() as d:
        s, b = d.submit(_doc(b_seed=4))
        assert s == 200 and b["ok"]
        assert b["plan"]["id"] is None
        assert b["plan"]["source"] == "flag-forced"
        assert d.status_doc()["plans"]["autotune"] is False
        assert d.status_doc()["plans"]["decisions"] == []
