"""Replica pool + health-aware router suite (DESIGN.md §18).

The failover contract under test: replicas built with the same engine seed
replay any rid's off-mode stream bit-for-bit, so a migrated request
continues token-for-token with NO re-emitted prefix — whether the old
replica was killed mid-decode, mid-chunked-prefill, wedged (no-progress
watchdog), or drained by a drift storm's guard telemetry. Every router
outcome is checked against a single-engine reference stream, never against
another router run.
"""

import asyncio
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core.faults import ReplicaFaultSpec
from repro.models.model import build
from repro.serving.engine import Engine, Request, RequestError
from repro.serving.frontend import Frontend
from repro.serving.router import (HealthPolicy, ReplicaRouter, build_pool)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-0.5b").reduced()
    cfg = dataclasses.replace(cfg, n_layers=2, d_model=128, d_ff=256,
                              vocab_size=128, n_heads=4, n_kv_heads=2,
                              head_dim=32)
    api = build(cfg)
    params, _ = api.init(jax.random.PRNGKey(0))
    return cfg, params


def _requests(cfg, n, rng, max_new=8, temps=(0.0, 0.8)):
    return [Request(prompt=rng.integers(0, cfg.vocab_size, 5 + (i % 7),
                                        dtype=np.int32),
                    max_new_tokens=max_new,
                    temperature=temps[i % len(temps)],
                    rid=f"req-{i}")
            for i in range(n)]


def _reference_streams(cfg, params, reqs, **kw):
    """Single-engine ground truth for the same rids (same seed=0)."""
    kw.setdefault("max_slots", len(reqs))
    kw.setdefault("max_len", 48)
    kw.setdefault("cim_mode", "off")
    eng = Engine(cfg, params, seed=0, **kw)
    clones = [Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                      temperature=r.temperature, rid=r.rid) for r in reqs]
    return eng.generate(clones)


def _pool(cfg, params, n, fault=None, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("cim_mode", "off")
    return build_pool(cfg, params, n, replica_fault=fault, **kw)


# -------------------------------------------------- cross-replica determinism


def test_same_rid_bit_identical_across_replicas(setup):
    """The determinism premise of migration: the same rid produces the same
    stream on ANY replica built with the same seed (off mode), including at
    temperature > 0 — sampling keys derive from (seed, crc32(rid)) only."""
    cfg, params = setup
    reqs = _requests(cfg, 4, np.random.default_rng(0))
    e0, e1 = _pool(cfg, params, 2, max_slots=4)
    a = e0.generate([Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                             temperature=r.temperature, rid=r.rid)
                     for r in reqs])
    b = e1.generate([Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                             temperature=r.temperature, rid=r.rid)
                     for r in reqs])
    assert a == b


def test_router_matches_single_engine(setup):
    """No faults: pool output per rid == single-engine output, regardless of
    which replica served it; replica attribution is populated."""
    cfg, params = setup
    reqs = _requests(cfg, 6, np.random.default_rng(1))
    ref = _reference_streams(cfg, params, reqs)
    router = ReplicaRouter(_pool(cfg, params, 3))
    out = router.generate(reqs)
    assert out == ref
    for r in reqs:
        assert router.replica_of(r) in {"r0", "r1", "r2"}
        assert router.migrations_of(r) == 0


# -------------------------------------------------------------- kill failover


def test_kill_mid_decode_migrates_bit_identical(setup):
    """Replica killed mid-decode: its in-flight requests migrate, replay on
    a healthy replica, and the delivered streams are token-identical to the
    unkilled single-engine reference — no re-emitted prefix, 0 lost."""
    cfg, params = setup
    reqs = _requests(cfg, 6, np.random.default_rng(2), max_new=10)
    ref = _reference_streams(cfg, params, reqs)
    fault = ReplicaFaultSpec(mode="kill", at_step=4, victim=1)
    router = ReplicaRouter(_pool(cfg, params, 3), replica_fault=fault)
    out = router.generate(reqs)
    assert out == ref
    kinds = [e["kind"] for e in router.events]
    assert "kill" in kinds and "dead" in kinds and "migrate" in kinds
    migrated = [r for r in reqs if router.migrations_of(r) > 0]
    assert migrated, "victim had in-flight work that must have migrated"
    # a migrate event fired only after tokens were already delivered
    # (mid-decode, not at submit)
    mig_events = [e for e in router.events if e["kind"] == "migrate"]
    assert any(e["delivered"] > 0 for e in mig_events)
    assert router.replica_states()[1]["state"] == "dead"


def test_kill_mid_chunked_prefill_migrates_bit_identical(setup):
    """Kill landing while the victim is still chunk-prefilling a long
    prompt: the replay must reproduce the full stream (prefill restarts on
    the new replica; nothing was delivered yet, so nothing re-emits)."""
    cfg, params = setup
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, 24,
                                        dtype=np.int32),
                    max_new_tokens=6, temperature=t, rid=f"long-{i}")
            for i, t in enumerate((0.0, 0.7))]
    ref = _reference_streams(cfg, params, reqs, chunk_size=4)
    fault = ReplicaFaultSpec(mode="kill", at_step=2, victim=0)
    router = ReplicaRouter(
        _pool(cfg, params, 2, max_slots=2, chunk_size=4),
        replica_fault=fault)
    out = router.generate(reqs)
    assert out == ref
    assert any(r for r in reqs if router.migrations_of(r) > 0)


def test_total_outage_fails_fast(setup):
    """Every replica dead -> pending requests fail with a route error
    instead of holding the pool open forever."""
    cfg, params = setup
    reqs = _requests(cfg, 2, np.random.default_rng(4))
    fault = ReplicaFaultSpec(mode="kill", at_step=1, victim=0)
    router = ReplicaRouter(_pool(cfg, params, 1, max_slots=4),
                           replica_fault=fault)
    out = router.generate(reqs)
    assert all(isinstance(o, RequestError) for o in out)
    assert all(o.phase == "route" for o in out)
    assert router.free_slots == 0


# ------------------------------------------------------------ wedge watchdog


def test_wedge_detected_and_migrated_bit_identical(setup):
    """A wedged replica raises nothing — step() 'succeeds' with no progress.
    Only the router's no-progress watchdog can tell; after wedge_patience
    stalled ticks the replica is declared dead and its work migrates."""
    cfg, params = setup
    reqs = _requests(cfg, 4, np.random.default_rng(5), max_new=10)
    ref = _reference_streams(cfg, params, reqs)
    fault = ReplicaFaultSpec(mode="wedge", at_step=3, victim=0)
    router = ReplicaRouter(
        _pool(cfg, params, 2, max_slots=2),
        health=HealthPolicy(wedge_patience=3), replica_fault=fault)
    out = router.generate(reqs)
    assert out == ref
    dead = [e for e in router.events if e["kind"] == "dead"]
    assert dead and "wedged" in dead[0]["reason"]
    assert any(router.migrations_of(r) > 0 for r in reqs)


# --------------------------------------------------------------- drift storm


def test_storm_drains_victim_and_completes(setup):
    """Drift-storm victim: no router-injected event at all — the victim's
    guard hard-trip telemetry drags its health score below drain_below, its
    in-flight work migrates, and every request still completes (the victim
    itself would finish via digital pinning; healthy replicas serve the
    stream the reference produces)."""
    cfg, params = setup
    reqs = _requests(cfg, 6, np.random.default_rng(6), max_new=8,
                     temps=(0.0,))
    fault = ReplicaFaultSpec(mode="storm", victim=1, storm_transient_mag=64.0)
    router = ReplicaRouter(
        _pool(cfg, params, 3, fault=fault, cim_mode="sim", guard=True),
        replica_fault=fault)
    out = router.generate(reqs)
    assert all(not isinstance(o, RequestError) for o in out)
    assert all(len(o) == r.max_new_tokens for o, r in zip(out, reqs))
    drains = [e for e in router.events if e["kind"] == "drain"]
    assert drains and all(e["replica"] == "r1" for e in drains)
    # storm victim is never killed: it is drained by telemetry, not faulted
    assert router.replica_states()[1]["state"] in ("draining", "healthy")


# -------------------------------------------------------- session API surface


def test_submit_validates_before_tracking(setup):
    """An invalid request must be rejected at submit and must NOT linger as
    pool work (the front-end relies on submit raising synchronously)."""
    cfg, params = setup
    router = ReplicaRouter(_pool(cfg, params, 2))
    bad = Request(prompt=np.arange(100, dtype=np.int32), max_new_tokens=10)
    with pytest.raises(ValueError):
        router.submit(bad)
    assert not router.has_work()


def test_cancel_and_status(setup):
    cfg, params = setup
    router = ReplicaRouter(_pool(cfg, params, 2))
    r = _requests(cfg, 1, np.random.default_rng(7))[0]
    router.submit(r)
    assert router.status_of(r) in ("queued", "running")
    assert router.cancel(r)
    assert router.status_of(r) == "cancelled"
    assert router.result_of(r) == []
    assert not router.cancel(r)


def test_frontend_over_router_kill_failover(setup):
    """The PR 8 Frontend fronts a pool unchanged; a mid-run replica kill is
    absorbed by migration and every record closes completed with replica
    attribution and a migration count."""
    cfg, params = setup
    reqs_seed = np.random.default_rng(8)
    fault = ReplicaFaultSpec(mode="kill", at_step=5, victim=0)
    router = ReplicaRouter(_pool(cfg, params, 2, max_slots=2),
                           replica_fault=fault)
    fe = Frontend(router, queue_limit=16)

    async def run():
        runner = asyncio.create_task(fe.run())
        tickets = [fe.submit(list(reqs_seed.integers(0, cfg.vocab_size, 6)),
                             8, rid=f"fe-{i}") for i in range(4)]
        await asyncio.gather(*(t.wait() for t in tickets))
        fe.stop()
        await runner
        return tickets

    tickets = asyncio.run(run())
    recs = [t.record for t in tickets]
    assert all(r.outcome == "completed" for r in recs)
    assert all(r.replica in ("r0", "r1") for r in recs)
    assert sum(r.migrations for r in recs) >= 1
    # streams match the single-engine reference for the same rids
    ref = _reference_streams(
        cfg, params,
        [Request(prompt=np.asarray(t.prompt, dtype=np.int32),
                 max_new_tokens=8, rid=t.rid) for t in tickets])
    assert [t.tokens for t in tickets] == ref


def test_failed_request_carries_replica_tag(setup):
    """RequestError.replica names the replica a failure is attributed to
    (serve.py prints it); router-level route errors stringify with it."""
    err = RequestError(reason="boom", phase="decode", replica="r2")
    assert "r2:" in str(err)


PLACEMENT_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import dataclasses, json
    import jax
    import numpy as np
    from repro.configs.registry import get_config
    from repro.models.model import build
    from repro.serving.engine import Engine, Request
    from repro.serving.router import ReplicaRouter, build_pool

    cfg = get_config("qwen2-0.5b").reduced()
    cfg = dataclasses.replace(cfg, n_layers=2, d_model=128, d_ff=256,
                              vocab_size=128, n_heads=4, n_kv_heads=2,
                              head_dim=32)
    params, _ = build(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 128, 5 + i, dtype=np.int32) for i in range(4)]

    def reqs():
        return [Request(prompt=p, max_new_tokens=6, temperature=0.7 * (i % 2),
                        rid=f"q{i}") for i, p in enumerate(prompts)]

    kw = dict(max_slots=2, max_len=32, cim_mode="off")
    devs = jax.devices()[:2]
    pool = build_pool(cfg, params, 2, devices=devs, **kw)

    def where(e):
        leaves = jax.tree.leaves((e.params, e.caches, e.last_tok))
        return sorted({str(d) for x in leaves for d in x.devices()})

    before = [where(e) for e in pool]
    out = ReplicaRouter(pool).generate(reqs())
    ref = Engine(cfg, params, **kw).generate(reqs())
    print(json.dumps({"devices": [str(d) for d in devs], "before": before,
                      "after": [where(e) for e in pool],
                      "same": out == ref}))
""")


def test_pool_replicas_committed_to_their_devices():
    """build_pool(devices=) commits each replica's params, cache and token
    state to its own device, where they stay while it serves; the pool's
    streams equal a single engine's. (Construction under a default-device
    context alone left every program on the first device.)"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", PLACEMENT_PROG], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    want = [[d] for d in res["devices"]]
    assert res["before"] == want
    assert res["after"] == want
    assert res["same"] is True
