"""Parked-replica pool: pool sizing, claim/adopt semantics against a
fake parked server, decision-audit records, and the tier-1 e2e smoke —
scale-from-zero attaches a Model to a real parked engine subprocess and
the completion round-trips."""

import json
import os
import sys
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubeai_tpu.api import model_types as mt  # noqa: E402
from kubeai_tpu.api.core_types import KIND_POD  # noqa: E402
from kubeai_tpu.api.model_types import Model, ModelSpec  # noqa: E402
from kubeai_tpu.autoscaler.autoscaler import DecisionLog  # noqa: E402
from kubeai_tpu.config.system import System  # noqa: E402
from kubeai_tpu.controller.parked import LABEL_PARKED, ParkedPool  # noqa: E402
from kubeai_tpu.runtime.store import ObjectMeta, Store  # noqa: E402


def _system(parked=2):
    system = System().default_and_validate()
    system.parked_replicas = parked
    return system


def test_pool_reconcile_creates_and_shrinks():
    store = Store()
    pool = ParkedPool(store, _system(parked=2))
    pool.reconcile()
    free = store.list(KIND_POD, "default", {LABEL_PARKED: "true"})
    assert len(free) == 2
    for p in free:
        assert p.spec.containers[0].args[0] == "--parked"
        assert mt.LABEL_MODEL not in p.meta.labels
    # Shrink when the operator lowers the knob.
    pool.system.parked_replicas = 1
    pool.reconcile()
    assert len(store.list(KIND_POD, "default", {LABEL_PARKED: "true"})) == 1
    # Idempotent at target.
    pool.reconcile()
    assert len(store.list(KIND_POD, "default", {LABEL_PARKED: "true"})) == 1


class _FakeParked(BaseHTTPRequestHandler):
    """Minimal parked-server stand-in: records /v1/attach bodies."""

    attaches: list = []
    accept = True

    def log_message(self, *a):
        pass

    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0))
        body = json.loads(self.rfile.read(n) or b"{}")
        type(self).attaches.append(body)
        code = 202 if type(self).accept else 409
        payload = json.dumps({"status": "attaching" if self.accept else "busy"}).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)


@pytest.fixture
def fake_parked_server():
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _FakeParked)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    _FakeParked.attaches = []
    _FakeParked.accept = True
    yield httpd
    httpd.shutdown()


def _desired_pod(model_name, pod_hash="abcd1234"):
    from kubeai_tpu.api.core_types import Container, Pod, PodSpec

    pod = Pod(
        meta=ObjectMeta(
            name="", labels={mt.LABEL_MODEL: model_name, mt.LABEL_POD_HASH: pod_hash}
        ),
        spec=PodSpec(
            containers=[
                Container(
                    name="server",
                    command=["python", "-m", "kubeai_tpu.engine.server"],
                    args=["--model", "/ckpt", "--served-model-name", model_name,
                          "--port", "8000"],
                )
            ]
        ),
    )
    return pod


def _seed_running_parked(store, pool, port):
    pool.reconcile()
    pod = store.list(KIND_POD, "default", {LABEL_PARKED: "true"})[0]

    def mutate(p):
        p.status.phase = "Running"
        p.status.pod_ip = "127.0.0.1"
        p.meta.annotations[mt.ANNOTATION_MODEL_POD_PORT] = str(port)

    store.mutate(KIND_POD, pod.meta.name, mutate, "default")
    return store.get(KIND_POD, pod.meta.name, "default")


def test_claim_adopts_and_records_decision(fake_parked_server):
    store = Store()
    log = DecisionLog()
    pool = ParkedPool(store, _system(parked=1), decision_log=log, clock=lambda: 123.0)
    pod = _seed_running_parked(store, pool, fake_parked_server.server_port)
    model = Model(meta=ObjectMeta(name="m1", uid="uid-1"), spec=ModelSpec(url="file:///ckpt"))
    desired = _desired_pod("m1")

    claimed = pool.claim(model, desired)
    assert claimed is not None and claimed.meta.name == pod.meta.name
    # The attach carried the desired pod's args verbatim.
    assert _FakeParked.attaches == [{"args": desired.spec.containers[0].args}]
    adopted = store.get(KIND_POD, pod.meta.name, "default")
    assert adopted.meta.labels[mt.LABEL_MODEL] == "m1"
    assert adopted.meta.labels[mt.LABEL_POD_HASH] == "abcd1234"
    assert adopted.meta.labels[LABEL_PARKED] == "attached"
    assert adopted.meta.owner_uids == ["uid-1"]
    assert adopted.status.ready is False  # not ready until /readyz says so
    # Audit record in the same log as scaling decisions.
    recs = log.snapshot(model="m1")
    assert recs and recs[0]["action"] == "parked_attach"
    assert recs[0]["pod"] == pod.meta.name
    assert recs[0]["t"] == 123.0
    # The adopted pod no longer counts as pool-free.
    assert store.list(KIND_POD, "default", {LABEL_PARKED: "true"}) == []


def test_claim_returns_none_when_no_pod_running(fake_parked_server):
    store = Store()
    pool = ParkedPool(store, _system(parked=1))
    pool.reconcile()  # pod exists but phase is not Running
    model = Model(meta=ObjectMeta(name="m1"), spec=ModelSpec(url="file:///x"))
    assert pool.claim(model, _desired_pod("m1")) is None
    assert _FakeParked.attaches == []


def test_claim_falls_back_when_attach_refused(fake_parked_server):
    _FakeParked.accept = False
    store = Store()
    pool = ParkedPool(store, _system(parked=1))
    pod = _seed_running_parked(store, pool, fake_parked_server.server_port)
    model = Model(meta=ObjectMeta(name="m1"), spec=ModelSpec(url="file:///x"))
    assert pool.claim(model, _desired_pod("m1")) is None
    # Refused pod keeps its parked label (not adopted).
    p = store.get(KIND_POD, pod.meta.name, "default")
    assert p.meta.labels[LABEL_PARKED] == "true"
    assert mt.LABEL_MODEL not in p.meta.labels


class _FakeFailedAttach(BaseHTTPRequestHandler):
    """Adopted parked pod whose attach died: /readyz 503 with the
    failure in the attach field (EngineServer's shape)."""

    attach_state = "failed: no such checkpoint"

    def log_message(self, *a):
        pass

    def do_GET(self):
        payload = json.dumps(
            {"status": "parked", "attach": type(self).attach_state}
        ).encode()
        self.send_response(503)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)


@pytest.mark.parametrize(
    "attach_state",
    [
        "failed: no such checkpoint",  # attach thread died
        # Process crashed mid-attach and was relaunched with its
        # original --parked args: an ADOPTED pod can never legitimately
        # read plain "parked", so the sweep must reclaim it too.
        "parked",
    ],
)
def test_sweep_deletes_failed_attach_pod(attach_state):
    # A claim stamped the pod with the CURRENT pod-hash, so the pod
    # planner will never replace it — the pool's sweep must delete it
    # (and audit why) so the model falls back to a normal create.
    _FakeFailedAttach.attach_state = attach_state
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _FakeFailedAttach)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        store = Store()
        log = DecisionLog()
        pool = ParkedPool(store, _system(parked=0), decision_log=log)
        from kubeai_tpu.api.core_types import Container, Pod, PodSpec

        pod = Pod(
            meta=ObjectMeta(
                name="parked-dead",
                labels={
                    LABEL_PARKED: "attached",
                    mt.LABEL_MODEL: "m1",
                    mt.LABEL_POD_HASH: "abcd1234",
                },
                annotations={
                    mt.ANNOTATION_MODEL_POD_PORT: str(httpd.server_port)
                },
            ),
            spec=PodSpec(containers=[Container(name="server")]),
        )
        store.create(KIND_POD, pod)

        def mutate(p):
            p.status.phase = "Running"
            p.status.pod_ip = "127.0.0.1"
            p.status.ready = False

        store.mutate(KIND_POD, "parked-dead", mutate, "default")
        pool.reconcile()
        assert store.list(KIND_POD, "default", {mt.LABEL_MODEL: "m1"}) == []
        recs = log.snapshot(model="m1")
        assert recs and recs[0]["action"] == "parked_attach_failed"
        assert recs[0]["error"] == attach_state
    finally:
        httpd.shutdown()


def test_sweep_leaves_inflight_attach_alone(fake_parked_server):
    # attach still "attaching" (the fake claim server's GET... use the
    # 404-less _FakeParked which only handles POST: GET raises -> the
    # sweep must treat unreachable/odd responses as in-flight, not
    # failure).
    store = Store()
    pool = ParkedPool(store, _system(parked=1))
    pod = _seed_running_parked(store, pool, fake_parked_server.server_port)

    def mutate(p):
        p.meta.labels[LABEL_PARKED] = "attached"
        p.meta.labels[mt.LABEL_MODEL] = "m1"
        p.status.ready = False

    store.mutate(KIND_POD, pod.meta.name, mutate, "default")
    pool.reconcile()
    assert store.list(KIND_POD, "default", {mt.LABEL_MODEL: "m1"}) != []


def test_claim_survives_unreachable_pod():
    store = Store()
    pool = ParkedPool(store, _system(parked=1), attach_timeout=0.3)
    _seed_running_parked(store, pool, 1)  # nothing listens on port 1
    model = Model(meta=ObjectMeta(name="m1"), spec=ModelSpec(url="file:///x"))
    assert pool.claim(model, _desired_pod("m1")) is None


# ---------------------------------------------------------------------------
# Tier-1 e2e: a real parked engine subprocess serves a scale-from-zero
# attach (ISSUE satellite: parked replica attach serves a completion).


@pytest.fixture(scope="module")
def ckpt_dir(tmp_path_factory):
    from kubeai_tpu.engine.weights import save_tiny_test_checkpoint

    path = tmp_path_factory.mktemp("ckpt")
    save_tiny_test_checkpoint(str(path))
    return str(path)


@pytest.mark.e2e
def test_parked_attach_serves_completion(ckpt_dir, tmp_path_factory):
    from kubeai_tpu.manager import Manager

    system = _system(parked=1)
    system.autoscaling.interval_seconds = 0.5
    mgr = Manager(system, local_runtime=True, host="127.0.0.1", port=0)
    mgr.local_runtime.extra_env["JAX_PLATFORMS"] = "cpu"
    mgr.local_runtime.extra_env["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("xla-cache")
    )
    mgr.start()
    try:
        # Wait for the parked pod's HTTP surface (jax import + server).
        deadline = time.time() + 180
        up = False
        while time.time() < deadline and not up:
            for p in mgr.store.list(KIND_POD, "default", {LABEL_PARKED: "true"}):
                port = p.meta.annotations.get(mt.ANNOTATION_MODEL_POD_PORT)
                if not port:
                    continue
                try:
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/health", timeout=1
                    ) as r:
                        up = json.loads(r.read()).get("parked", False)
                except Exception:
                    pass
            time.sleep(0.5)
        assert up, "parked pod HTTP never came up"

        mgr.store.create(
            mt.KIND_MODEL,
            Model(
                meta=ObjectMeta(name="tiny-parked"),
                spec=ModelSpec(
                    url=f"file://{ckpt_dir}",
                    engine=mt.ENGINE_TPU,
                    resource_profile="cpu:1",
                    min_replicas=1,
                    args=["--max-seq-len", "128", "--max-slots", "2"],
                ),
            ),
        )
        body = json.dumps(
            {"model": "tiny-parked", "prompt": "hello", "max_tokens": 3}
        ).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{mgr.api.port}/openai/v1/completions",
            data=body, headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=400) as resp:
            out = json.loads(resp.read())
        assert out["choices"][0]["finish_reason"] in ("length", "stop")

        # The serving pod IS the adopted parked pod.
        pods = mgr.store.list(KIND_POD, "default", {mt.LABEL_MODEL: "tiny-parked"})
        assert pods and pods[0].meta.labels.get(LABEL_PARKED) == "attached"
        assert pods[0].meta.name.startswith("parked-")

        # The attach decision is visible in the autoscaler audit.
        with urllib.request.urlopen(
            f"http://127.0.0.1:{mgr.api.port}/debug/autoscaler?model=tiny-parked",
            timeout=10,
        ) as r:
            recs = json.loads(r.read())["decisions"]
        attaches = [x for x in recs if x.get("action") == "parked_attach"]
        assert attaches and attaches[0]["pod"] == pods[0].meta.name
    finally:
        mgr.stop()
