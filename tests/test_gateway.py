"""Gateway-tier tests (ISSUE 17): auth helper, tenancy, weighted-fair
queueing, prefix-affinity routing, hedging, cell-down replay, and
/metrics federation.

The jax-free half (auth / tenancy / WFQ) runs on hand-built queues with
a fake clock — no device, microseconds each. The engine-backed half
builds tiny two-cell gateways (the test_serve.py tiny config, total_len
24) and pins the tentpole contracts: repeated prompts land warm via the
content-addressed rendezvous key, a dead cell's flights replay on the
survivor with byte-identical tokens and zero loss, the hedge race is
first-fulfill-wins, and the gateway's federated /metrics samples sum to
exactly what the cells' own /stats report.
"""

import json
import time

import pytest

from dalle_pytorch_tpu.resilience import faults
from dalle_pytorch_tpu.serve import auth
from dalle_pytorch_tpu.serve import prefix_cache as PC
from dalle_pytorch_tpu.serve import scheduler as S
from dalle_pytorch_tpu.serve import tenancy as T


# ---------------------------------------------------------------------------
# auth helper (satellite: the one constant-time token check)
# ---------------------------------------------------------------------------

class TestAuth:
    def test_check_token(self):
        assert auth.check_token("secret", "secret")
        assert not auth.check_token("secret", "other")
        assert not auth.check_token("", "secret")

    def test_empty_expected_always_refuses(self):
        # an unconfigured secret is a refusal, never a wildcard
        assert not auth.check_token("", "")
        assert not auth.check_token("anything", "")

    def test_non_strings_refused(self):
        assert not auth.check_token(None, "secret")
        assert not auth.check_token(["secret"], "secret")
        assert not auth.check_token("secret", None)

    def test_http_token_bearer_wins(self):
        headers = {"Authorization": "Bearer abc", "X-Admin-Token": "z"}
        assert auth.http_token(headers) == "abc"
        assert auth.http_token({"X-Admin-Token": "z"}) == "z"
        assert auth.http_token({}) == ""
        assert auth.http_token({"X-API-Key": "k"}, "X-API-Key") == "k"

    def test_check_http(self):
        assert auth.check_http({"Authorization": "Bearer t"}, "t")
        assert not auth.check_http({}, "t")


# ---------------------------------------------------------------------------
# tenancy: specs, buckets, table, quotas
# ---------------------------------------------------------------------------

class TestTenancy:
    def test_spec_validation(self):
        with pytest.raises(ValueError):
            T.TenantSpec(name="")
        with pytest.raises(ValueError):
            T.TenantSpec(name="a", weight=0)
        with pytest.raises(ValueError):
            T.TenantSpec(name="a", tier="platinum")

    def test_tier_hedge_defaults(self):
        assert T.TenantSpec(name="a", tier="gold").hedge_after_s \
            == T.TIERS["gold"]
        assert T.TenantSpec(name="a", tier="bronze").hedge_after_s \
            is None
        assert T.TenantSpec(name="a", tier="bronze",
                            hedge_s=0.5).hedge_after_s == 0.5

    def test_token_bucket_refill(self):
        clock = [0.0]
        tb = T.TokenBucket(2.0, clock=lambda: clock[0])
        assert tb.take() == 0.0 and tb.take() == 0.0
        retry = tb.take()
        assert retry > 0.0
        clock[0] += retry
        assert tb.take() == 0.0

    def test_token_bucket_zero_rate_unlimited(self):
        tb = T.TokenBucket(0.0, clock=lambda: 0.0)
        assert all(tb.take() == 0.0 for _ in range(100))

    def test_table_from_json_and_authenticate(self):
        tbl = T.TenantTable.from_json({"tenants": [
            {"name": "a", "key": "ka"}, {"name": "b", "key": "kb"}]})
        assert tbl.names() == ["a", "b"]
        assert tbl.authenticate("kb").name == "b"
        with pytest.raises(T.AuthError) as ei:
            tbl.authenticate("wrong")
        assert ei.value.record["kind"] == "gateway_auth_failed"

    def test_duplicate_names_rejected(self):
        with pytest.raises(ValueError):
            T.TenantTable.from_json([{"name": "a"}, {"name": "a"}])

    def test_open_tenant_matches_empty_key_only(self):
        tbl = T.TenantTable.from_json([{"name": "dev"}])
        assert tbl.authenticate("").name == "dev"
        with pytest.raises(T.AuthError):
            tbl.authenticate("guess")

    def test_rps_throttle_typed_with_retry_after(self):
        clock = [0.0]
        tbl = T.TenantTable.from_json(
            [{"name": "a", "key": "k", "rps": 1.0}],
            clock=lambda: clock[0])
        tbl.admit("a", image_tokens=0, pages=0)
        with pytest.raises(T.TenantThrottled) as ei:
            tbl.admit("a", image_tokens=0, pages=0)
        rec = ei.value.record
        assert rec["kind"] == "tenant_throttled"
        assert rec["quota"] == "rps"
        assert ei.value.retry_after_s > 0.0
        clock[0] += ei.value.retry_after_s
        tbl.admit("a", image_tokens=0, pages=0)    # refilled

    def test_page_budget_all_or_nothing(self):
        tbl = T.TenantTable.from_json(
            [{"name": "a", "key": "k", "max_pages": 4}],
            clock=lambda: 0.0)
        tbl.admit("a", image_tokens=0, pages=4)
        with pytest.raises(T.TenantThrottled) as ei:
            tbl.admit("a", image_tokens=0, pages=1)
        assert ei.value.record["quota"] == "pages"
        tbl.release("a", pages=4)
        tbl.admit("a", image_tokens=0, pages=4)    # budget returned
        assert tbl.stats()["a"]["pages_in_flight"] == 4

    def test_reload_keeps_ledger_for_persisting_tenants(self):
        clock = [0.0]
        tbl = T.TenantTable.from_json(
            [{"name": "a", "key": "k", "rps": 1.0, "max_pages": 8}],
            clock=lambda: clock[0])
        tbl.admit("a", image_tokens=0, pages=3)
        with pytest.raises(T.TenantThrottled):
            tbl.admit("a", image_tokens=0, pages=1)   # rps spent
        rec = tbl.reload([{"name": "a", "key": "k2", "rps": 1.0,
                           "max_pages": 8},
                          {"name": "b", "key": "kb"}])
        assert rec["added"] == ["b"] and rec["removed"] == []
        # the spent bucket did NOT reset with the reload
        with pytest.raises(T.TenantThrottled):
            tbl.admit("a", image_tokens=0, pages=1)
        # pages reserved before the reload still count
        assert tbl.stats()["a"]["pages_in_flight"] == 3
        # the new key authenticates, the old one no longer does
        assert tbl.authenticate("k2").name == "a"
        with pytest.raises(T.AuthError):
            tbl.authenticate("k")


# ---------------------------------------------------------------------------
# weighted-fair queueing (satellite: 2:1 share, no permanent debt)
# ---------------------------------------------------------------------------

def _wfq(weights, **kw):
    return S.WeightedFairQueue(
        max_depth=kw.pop("max_depth", 512),
        clock=kw.pop("clock", lambda: 0.0),
        weight_of=lambda t: weights.get(t, 1.0), **kw)


_TOKEN_COST = {"a": 256.0, "b": 64.0, "big": 256.0, "small": 256.0}


def _token_cost(request):
    """Per-request decode cost in image tokens — the gateway's fairness
    unit. Tenants here carry DIFFERENT per-request costs (a
    variable-resolution fleet), which is exactly the case where
    request-count shares and token shares diverge."""
    return _TOKEN_COST[request.tenant]


class TestWeightedFairQueue:
    def test_two_to_one_share_under_saturation(self):
        # two tenants at weights 2:1, both with deep backlogs — but
        # tenant a's requests cost 4x the tokens of tenant b's
        # (256 vs 64): the drain order must give the weight-2 tenant
        # 2/3 of the service IN TOKENS within 10% — which means only
        # ~1/3 of the popped REQUESTS. Asserting request counts here
        # would reward exactly the fan-out gaming the token cost_fn
        # exists to close
        for n in (15, 30, 60):     # every prefix of the drain is fair
            qq = _wfq({"a": 2.0, "b": 1.0}, cost_fn=_token_cost)
            for _ in range(120):
                qq.submit(S.Request(codes=(1,), tenant="a"))
                qq.submit(S.Request(codes=(1,), tenant="b"))
            ready, _ = qq.pop_ready(n)
            tok = {"a": 0.0, "b": 0.0}
            for h in ready:
                tok[h.request.tenant] += _token_cost(h.request)
            share = tok["a"] / (tok["a"] + tok["b"])
            # one 256-token pop is a big quantum at small n: allow one
            # request's worth of slack on top of the 10% bar
            assert abs(share - 2 / 3) <= 0.1 * (2 / 3) \
                + 256.0 / (tok["a"] + tok["b"]), (n, share)
            # and the request-count share is NOT 2/3 — a's requests are
            # 4x heavier, so it gets 2/3 of the tokens via ~1/3 of the
            # pops (the satellite's point, pinned)
            req_share = sum(1 for h in ready
                            if h.request.tenant == "a") / n
            assert req_share < 0.5, (n, req_share)

    def test_weighted_share_is_work_proportional(self):
        # equal per-request cost: token shares and the 3:1 weights
        # agree — 75% of the serviced tokens go to the weight-3 tenant
        q = _wfq({"big": 3.0, "small": 1.0}, cost_fn=_token_cost)
        for _ in range(80):
            q.submit(S.Request(codes=(1,), tenant="big"))
            q.submit(S.Request(codes=(1,), tenant="small"))
        ready, _ = q.pop_ready(40)
        tok = {"big": 0.0, "small": 0.0}
        for h in ready:
            tok[h.request.tenant] += _token_cost(h.request)
        assert abs(tok["big"] / (tok["big"] + tok["small"]) - 0.75) \
            <= 0.1

    def test_no_permanent_debt_after_idle(self):
        # a tenant whose backlog pushed its finish tag far ahead goes
        # idle; after the OTHER tenant advances virtual time past it,
        # a fresh submit must start at V (caught up), not pay old debt
        q = _wfq({"a": 1.0, "b": 1.0}, cost_fn=_token_cost)
        for _ in range(20):
            q.submit(S.Request(codes=(1,), tenant="a"))
        q.pop_ready(20)                       # drain a's backlog
        tag_a = q.finish_tag("a")
        assert tag_a > q.virtual_time()       # tag raced ahead of V
        for _ in range(40):
            q.submit(S.Request(codes=(1,), tenant="b"))
        q.pop_ready(40)                       # V advances past tag_a
        assert q.virtual_time() > tag_a
        h = q.submit(S.Request(codes=(1,), tenant="a"))
        # caught up: the new start tag is V, not the stale finish tag;
        # the finish tag sits one request's TOKEN cost (over weight)
        # ahead — virtual time is token-denominated now
        assert h.vstart == pytest.approx(q.virtual_time())
        assert h.vfinish == pytest.approx(
            h.vstart + _token_cost(h.request))

    def test_gateway_charges_image_tokens(self, bundle):
        # the gateway's WFQ must charge cfg.image_seq_len per request
        # (fairness in decoded work), not 1.0: a submitted handle's
        # finish tag advances by image tokens over weight
        _, _, cfg = bundle
        gw = _gateway(bundle, n_cells=1)
        try:
            h = gw.submit((1, 2), seed=0)
            assert h.vfinish - h.vstart == pytest.approx(
                float(cfg.image_seq_len))
            assert h.result(90).ok
        finally:
            gw.close()

    def test_no_banked_credit_from_idle(self):
        # an idle tenant must NOT accumulate credit while others run:
        # its first submit starts at V, so it cannot monopolize the
        # queue to "catch up" on service it never asked for
        q = _wfq({"a": 1.0, "b": 1.0})
        for _ in range(30):
            q.submit(S.Request(codes=(1,), tenant="b"))
        q.pop_ready(30)
        v = q.virtual_time()
        h = q.submit(S.Request(codes=(1,), tenant="a"))
        assert h.vstart == pytest.approx(v)

    def test_priority_dominates_fairness(self):
        q = _wfq({"a": 1.0, "b": 100.0})
        q.submit(S.Request(codes=(1,), tenant="b", priority=1))
        h = q.submit(S.Request(codes=(1,), tenant="a", priority=0))
        ready, _ = q.pop_ready(1)
        assert ready[0] is h

    def test_requeue_preserves_virtual_position(self):
        # eviction/failover requeue must re-enter at the ORIGINAL
        # virtual finish tag (cached on the handle) — replay
        # determinism and no-starvation both hang on this
        q = _wfq({"a": 1.0, "b": 1.0})
        h1 = q.submit(S.Request(codes=(1,), tenant="a"))
        tag = h1.vfinish
        for _ in range(10):
            q.submit(S.Request(codes=(1,), tenant="b"))
        popped, _ = q.pop_ready(1)
        assert popped[0] is h1
        q.requeue(h1)
        assert h1.vfinish == tag              # tag survived the trip
        ready, _ = q.pop_ready(1)
        assert ready[0] is h1                 # still first in line

    def test_base_queue_ordering_unchanged(self):
        # the refactor hook must leave the plain queue byte-identical:
        # (priority, arrival) order, tenants ignored
        q = S.RequestQueue(max_depth=16, clock=lambda: 0.0)
        h1 = q.submit(S.Request(codes=(1,), tenant="z", priority=1))
        h2 = q.submit(S.Request(codes=(1,), tenant="a", priority=0))
        h3 = q.submit(S.Request(codes=(1,), tenant="m", priority=0))
        ready, _ = q.pop_ready(3)
        assert ready == [h2, h3, h1]

    def test_tenant_rides_the_wire(self):
        r = S.Request(codes=(1, 2), tenant="acme")
        d = r.to_wire(now=0.0)
        assert d["tenant"] == "acme"
        back = S.Request.from_wire(d, now=1.0)
        assert back.tenant == "acme"
        # pre-tenancy frames decode as the anonymous tenant
        del d["tenant"]
        assert S.Request.from_wire(d, now=1.0).tenant == ""


# ---------------------------------------------------------------------------
# routing key + fault rows (jax-free)
# ---------------------------------------------------------------------------

class TestRoutingPlumbing:
    def test_content_key_matches_engine_key(self):
        from dalle_pytorch_tpu.models import dalle as D
        from dalle_pytorch_tpu.models import vae as V
        vcfg = V.VAEConfig(image_size=16, num_tokens=32,
                           codebook_dim=16, num_layers=2, hidden_dim=8)
        cfg = D.DALLEConfig(dim=16, depth=2, vae=vcfg,
                            num_text_tokens=50, text_seq_len=8,
                            heads=2, dim_head=8)
        codes = (3, 4, 5)
        want = PC.prefix_key(
            codes, model_version="v0",
            layer_sig=PC.layer_signature(cfg.transformer),
            quantized=False)
        assert PC.content_key(codes, cfg=cfg,
                              model_version="v0") == want
        # and the transformer config works directly too
        assert PC.content_key(codes, cfg=cfg.transformer,
                              model_version="v0") == want
        # different version -> different cell affinity
        assert PC.content_key(codes, cfg=cfg,
                              model_version="v1") != want

    def test_gateway_fault_rows_fire_once(self):
        with faults.injected(gateway_cell_down_at_request=2):
            assert not faults.on_gateway_dispatch(1)
            assert faults.on_gateway_dispatch(2)
            assert not faults.on_gateway_dispatch(3)   # fire-once
        assert not faults.on_gateway_dispatch(99)      # no plan
        with faults.injected(tenant_flood="abuser",
                             tenant_flood_requests=7):
            spec = faults.gateway_flood()
            assert spec == {"tenant": "abuser", "requests": 7}
            assert faults.gateway_flood() is None      # fire-once
        assert faults.gateway_flood() is None

    def test_fault_plan_env_round_trip(self):
        plan = faults.FaultPlan(gateway_cell_down_at_request=3,
                                tenant_flood="t", tenant_flood_requests=5)
        blob = json.dumps({"gateway_cell_down_at_request": 3,
                           "tenant_flood": "t",
                           "tenant_flood_requests": 5})
        assert faults.FaultPlan(**json.loads(blob)) == plan


# ---------------------------------------------------------------------------
# engine-backed gateway tests (tiny model, CPU)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bundle():
    import jax
    from dalle_pytorch_tpu.models import dalle as D
    from dalle_pytorch_tpu.models import vae as V
    vcfg = V.VAEConfig(image_size=16, num_tokens=32, codebook_dim=16,
                       num_layers=2, hidden_dim=8)
    cfg = D.DALLEConfig(dim=16, depth=2, vae=vcfg, num_text_tokens=50,
                        text_seq_len=8, heads=2, dim_head=8)
    key = jax.random.PRNGKey(0)
    vae_params = V.vae_init(jax.random.fold_in(key, 1), vcfg)
    params = D.dalle_init(key, cfg, vae_params)
    return params, vae_params, cfg


def _cell(bundle, **kw):
    from dalle_pytorch_tpu.serve.server import InferenceServer
    params, vae_params, cfg = bundle
    kw.setdefault("num_slots", 2)
    kw.setdefault("queue_depth", 16)
    kw.setdefault("kv", "paged")
    kw.setdefault("page_size", 4)
    kw.setdefault("prefix_cache", True)
    kw.setdefault("decode_images", False)
    kw.setdefault("weights_version", "v0")
    return InferenceServer(params, vae_params, cfg, **kw).start()


def _gateway(bundle, n_cells=2, **kw):
    from dalle_pytorch_tpu.serve.gateway import Gateway
    _, _, cfg = bundle
    cells = [_cell(bundle) for _ in range(n_cells)]
    kw.setdefault("cfg", cfg)
    kw.setdefault("model_version", "v0")
    kw.setdefault("queue_depth", 64)
    kw.setdefault("pages_per_request", 6)
    return Gateway(cells, **kw).start()


class TestGateway:
    def test_affinity_routes_repeats_warm(self, bundle):
        gw = _gateway(bundle)
        try:
            prompt = (3, 4, 5)
            # waves of <= capacity so the affine cell is never
            # saturated: every wave after the first admits warm on
            # the SAME cell
            for wave in range(3):
                hs = [gw.submit(prompt, seed=7) for _ in range(2)]
                for h in hs:
                    assert h.result(90).ok
            routes = gw.events("gateway_route")
            assert len(routes) == 6
            assert len({e["cell"] for e in routes}) == 1
            assert all(e["affine"] for e in routes)
            st = gw.stats()
            assert st["fleet"]["prefix_hits"] >= 4
            assert st["spills"] == 0
        finally:
            gw.close()

    def test_affinity_hits_more_than_hash_blind_on_repeats(self, bundle):
        # the same two prompts, five waves, order rotated a wave,
        # through two fresh fleets: affinity sends a repeat to the
        # cell that holds its pages; least-loaded placement follows
        # arrival order, which the rotation scrambles, so each prompt
        # pays a cold prefill on both cells
        prompts = [(1,) * 4, (2,) * 4]
        rates = {}
        for affinity in (True, False):
            gw = _gateway(bundle, affinity=affinity)
            try:
                for wave in range(5):
                    order = prompts if wave % 2 == 0 else prompts[::-1]
                    for h in [gw.submit(p, seed=0) for p in order]:
                        assert h.result(90).ok
                st = gw.stats()
                assert st["fleet"]["completed"] == 10
                rates[affinity] = st["fleet_prefix_hit_rate"]
            finally:
                gw.close()
        assert rates[True] > rates[False], rates

    def test_spill_when_affine_cell_saturated(self, bundle):
        gw = _gateway(bundle)
        try:
            prompt = (6, 7)
            hs = [gw.submit(prompt, seed=1) for _ in range(4)]
            for h in hs:
                assert h.result(90).ok
            # 4 same-key requests against capacity-2 cells: the two
            # that couldn't fit on the affine cell spilled, typed
            assert gw.spills >= 1
            spills = gw.events("gateway_spill")
            assert spills and spills[0]["affine"] != spills[0]["cell"]
            routes = gw.events("gateway_route")
            assert len({e["cell"] for e in routes}) == 2
        finally:
            gw.close()

    def test_replay_identical_same_seed(self, bundle):
        gw = _gateway(bundle)
        try:
            rs = [gw.generate((9, 2, 4), seed=3, timeout=90)
                  for _ in range(3)]
            assert all(r.ok for r in rs)
            toks = {tuple(int(t) for t in r.tokens) for r in rs}
            assert len(toks) == 1
        finally:
            gw.close()

    def test_cell_down_replays_zero_loss(self, bundle):
        # the gateway_cell_down_at_request fault row: the cell that
        # received the first dispatch dies whole mid-stream; every
        # request it held must complete OK on the survivor via requeue
        # + replay — zero loss, and the fence is a typed event
        gw = _gateway(bundle)
        try:
            with faults.injected(gateway_cell_down_at_request=1):
                hs = [gw.submit((5, 5, 5), seed=11) for _ in range(3)]
                rs = [h.result(120) for h in hs]
            assert [r.status for r in rs] == ["ok"] * 3
            toks = {tuple(int(t) for t in r.tokens) for r in rs}
            assert len(toks) == 1          # replay byte-identical
            assert gw.cell_downs == 1
            assert gw.replays >= 1
            assert gw.events("gateway_cell_down")
            assert gw.events("gateway_replay")
            assert sum(1 for c in gw.cells if c.alive()) == 1
        finally:
            gw.close()

    def test_hedged_send_first_fulfill_wins(self, bundle):
        # hedge_s=0: every dispatch hedges on the next sweep; the
        # first arm to finish fulfils the caller (first-write-wins),
        # the loser is cooperatively cancelled — result still OK and
        # byte-identical to the unhedged run
        tbl = T.TenantTable.from_json(
            [{"name": "gold", "key": "kg", "tier": "gold",
              "hedge_s": 0.0}])
        gw = _gateway(bundle, tenants=tbl, hedge_check_s=0.0)
        try:
            baseline = gw.generate((1, 2, 3), api_key="kg", seed=5,
                                   timeout=90)
            assert baseline.ok
            r = gw.generate((8, 1, 2), api_key="kg", seed=5,
                            timeout=90)
            assert r.ok
            assert gw.hedges >= 1
            assert gw.events("gateway_hedge")
        finally:
            gw.close()

    def test_tenant_flood_isolation(self, bundle):
        # the degradation contract, unit-sized: the abusive tenant
        # exhausts its own rps quota (typed 429 + retry-after), the
        # victim's requests all complete
        tbl = T.TenantTable.from_json([
            {"name": "victim", "key": "kv", "weight": 2},
            {"name": "abuser", "key": "ka", "weight": 1, "rps": 2.0}])
        gw = _gateway(bundle, tenants=tbl)
        try:
            throttled = 0
            with faults.injected(tenant_flood="abuser",
                                 tenant_flood_requests=12):
                flood = faults.gateway_flood()
                assert flood["tenant"] == "abuser"
                flood_handles = []
                for i in range(flood["requests"]):
                    try:
                        flood_handles.append(
                            gw.submit((7, 7), api_key="ka", seed=i))
                    except T.TenantThrottled as e:
                        assert e.record["kind"] == "tenant_throttled"
                        assert e.retry_after_s > 0.0
                        throttled += 1
                victims = [gw.submit((2, 2, 2), api_key="kv", seed=0)
                           for _ in range(2)]
                assert all(h.result(120).ok for h in victims)
            assert throttled > 0
            assert gw.tenants.stats()["abuser"]["throttled"] \
                == throttled
            for h in flood_handles:    # admitted flood still completes
                assert h.result(120).status == S.OK
        finally:
            gw.close()

    def test_metrics_federation_pins_cell_sums(self, bundle):
        # satellite 6: sum of the per-cell samples the gateway
        # federates == the unlabeled fleet sample == what the cells'
        # own /stats report; tenant labels present on the gateway
        # counters and the latency histogram
        tbl = T.TenantTable.from_json(
            [{"name": "acme", "key": "k1"}])
        gw = _gateway(bundle, tenants=tbl)
        try:
            for i in range(4):
                assert gw.generate((1, 1, i + 1), api_key="k1",
                                   timeout=90).ok
            text = gw.metrics_text()
            want_sum = sum(c.server.stats()["completed"]
                           for c in gw.cells)
            per_cell, fleet = {}, None
            for line in text.splitlines():
                if not line.startswith(
                        "dalle_serve_requests_completed_total"):
                    continue
                name, value = line.rsplit(" ", 1)
                if "cell=" in name:
                    per_cell[name] = float(value)
                else:
                    fleet = float(value)
            assert per_cell and fleet is not None
            assert sum(per_cell.values()) == fleet == want_sum == 4
            assert 'dalle_gateway_tenant_admitted_total' \
                   '{tenant="acme"} 4' in text
            assert 'dalle_gateway_e2e_latency_seconds' in text
            assert 'tenant="acme"' in text
        finally:
            gw.close()

    def test_gateway_http_surface(self, bundle):
        # POST /generate with an API key, 401 on a bad key, 429 with
        # Retry-After on throttle, authenticated /admin/tenants hot
        # reload, /metrics and /tenants exposition
        import urllib.error
        import urllib.request
        from dalle_pytorch_tpu.serve.gateway import (
            make_gateway_http_server)
        tbl = T.TenantTable.from_json(
            [{"name": "acme", "key": "k1", "rps": 2.0}])
        gw = _gateway(bundle, tenants=tbl,
                      admin_token="admintok")
        httpd = make_gateway_http_server(gw, port=0)
        host, port = httpd.server_address[:2]
        import threading
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()

        def post(path, body, headers=None, timeout=90):
            req = urllib.request.Request(
                f"http://{host}:{port}{path}",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json",
                         **(headers or {})})
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, json.loads(r.read()), dict(r.headers)

        try:
            code, body, _ = post("/generate", {"codes": [1, 2]},
                                 {"X-API-Key": "k1"})
            assert code == 200 and body["status"] == "ok"
            with pytest.raises(urllib.error.HTTPError) as ei:
                post("/generate", {"codes": [1, 2]},
                     {"X-API-Key": "bad"})
            assert ei.value.code == 401
            # burn the rps bucket -> typed 429 with Retry-After
            got_429 = None
            for _ in range(4):
                try:
                    post("/generate", {"codes": [3, 3]},
                         {"X-API-Key": "k1"})
                except urllib.error.HTTPError as e:
                    if e.code == 429:
                        got_429 = e
                        break
            assert got_429 is not None
            assert got_429.headers.get("Retry-After") is not None
            assert json.loads(got_429.read())["kind"] \
                == "tenant_throttled"
            # hot reload: 401 without the admin token, 200 with
            with pytest.raises(urllib.error.HTTPError) as ei:
                post("/admin/tenants", [{"name": "acme", "key": "k2"}])
            assert ei.value.code == 401
            # rps: 0.0 lifts the limit — and because the ledger
            # persists across reloads, anything else would leave the
            # spent bucket spent (the anti-washing contract)
            code, body, _ = post(
                "/admin/tenants",
                [{"name": "acme", "key": "k2", "rps": 0.0}],
                {"Authorization": "Bearer admintok"})
            assert code == 200 and body["tenants"] == ["acme"]
            code, body, _ = post("/generate", {"codes": [1, 2]},
                                 {"X-API-Key": "k2"})
            assert code == 200 and body["status"] == "ok"
            with urllib.request.urlopen(
                    f"http://{host}:{port}/tenants", timeout=10) as r:
                assert "acme" in json.loads(r.read())["tenants"]
            with urllib.request.urlopen(
                    f"http://{host}:{port}/metrics", timeout=10) as r:
                assert b"dalle_gateway_routed_total" in r.read()
        finally:
            httpd.shutdown()
            httpd.server_close()
            gw.close()


class TestStreamingFanoutGateway:
    """Fan-out and streams through the fleet door (ISSUE 20): the WFQ
    charges decoded work (n_samples x the per-sample span), tenant page
    budgets charge the COW footprint (one prompt span + N generation
    spans, not N cold prefills), hedging is a typed reject for live
    streams, and a streamed best-of-N round trip feeds the
    gateway-owned sinks end to end."""

    def test_wfq_charges_n_samples_times_span(self, bundle):
        # fairness stays decoded-work-denominated under fan-out: a
        # best-of-3 advances the finish tag by 3x the span, and a
        # short-grid override charges its shorter span — neither
        # splitting nor shrinking work can game the share
        _, _, cfg = bundle
        gw = _gateway(bundle, n_cells=1)
        try:
            h = gw.submit((1, 2), seed=0, n_samples=3)
            assert h.vfinish - h.vstart == pytest.approx(
                3.0 * cfg.image_seq_len)
            h2 = gw.submit((1, 3), seed=0, n_samples=2,
                           image_seq_len_override=8)
            assert h2.vfinish - h2.vstart == pytest.approx(2.0 * 8)
            assert h.result(120).ok and h2.result(120).ok
        finally:
            gw.close()

    def test_tenant_pages_charge_cow_footprint(self, bundle):
        # the page reservation models the COW group: tiny cfg has
        # text=8 + image=16 = 24 positions, base 6 pages per request.
        # best-of-4 shares ONE prompt span: (8 + 4*16)/24 * 6 = 18
        # pages — strictly under the 24 four cold prefills would cost
        gw = _gateway(bundle, n_cells=1)
        try:
            base = gw.pages_per_request
            assert gw._flight_pages(1, 0) == base == 6
            assert gw._flight_pages(4, 0) == 18 < 4 * base
            # a short-grid override shrinks the generation share
            assert gw._flight_pages(4, 8) == 10
            assert gw._flight_pages(1, 8) == 4 < base
            # without a cfg the geometry is unknown: conservative N x
            saved = gw.cfg
            gw.cfg = None
            try:
                assert gw._flight_pages(4, 0) == 4 * base
            finally:
                gw.cfg = saved
        finally:
            gw.close()

    def test_hedge_is_typed_reject_for_streams(self, bundle):
        # hedge_s=0 would hedge every dispatch — but two live arms
        # would both feed the client's sinks. The stream keeps its
        # single arm; the refusal is a typed event + counter, and the
        # request still completes OK
        tbl = T.TenantTable.from_json(
            [{"name": "gold", "key": "kg", "tier": "gold",
              "hedge_s": 0.0}])
        gw = _gateway(bundle, tenants=tbl, hedge_check_s=0.0)
        try:
            h = gw.submit((4, 2, 1), api_key="kg", seed=3,
                          stream=True)
            assert h.result(120).ok
            assert gw.hedge_stream_rejects >= 1
            evs = gw.events("gateway_hedge_reject")
            assert evs and evs[0]["reason"] == "stream"
            assert not gw.events("gateway_hedge")
            assert gw.stats()["hedge_stream_rejects"] >= 1
            assert "dalle_gateway_hedge_stream_rejects_total" \
                in gw.metrics_text()
        finally:
            gw.close()

    def test_streamed_best_of_n_end_to_end(self, bundle):
        # gateway-owned sinks (replay-safe) deliver both samples'
        # token events and group-atomic sample_done frames; the
        # flight's terminal returns the COW page reservation and the
        # streams_active gauge drains back to zero
        _, _, cfg = bundle
        tbl = T.TenantTable.from_json(
            [{"name": "acme", "key": "k", "max_pages": 64}])
        gw = _gateway(bundle, n_cells=1, tenants=tbl)
        try:
            h = gw.submit((2, 3, 4), api_key="k", seed=9,
                          stream=True, n_samples=2)
            sink = gw._flights[h.request.request_id].sinks[0]
            assert sink.replayable
            seen, done_samples = {}, []
            for ev in sink.events():
                if ev["event"] == "tokens":
                    seen.setdefault(ev["sample"], {})[ev["pos"]] \
                        = ev["tokens"]
                elif ev["event"] == "sample_done":
                    done_samples.append(ev["sample"])
            res = h.result(120)
            assert res.ok and len(res.tokens) == cfg.image_seq_len
            assert sorted(done_samples) == [0, 1]
            for s in (0, 1):
                toks = []
                for pos in sorted(seen[s]):
                    toks.extend(seen[s][pos])
                assert len(toks) >= cfg.image_seq_len
            assert tbl.stats()["acme"]["pages_in_flight"] == 0
            st = gw.stats()
            assert st["streams_active"] == 0 and st["completed"] >= 1
            assert "dalle_gateway_streams_active" \
                in gw.metrics_text()
        finally:
            gw.close()


class TestCellStatsSurface:
    def test_replica_set_aggregates_prefix_stats(self, bundle):
        # the cell-stats satellite: a ReplicaSet-backed cell exposes
        # fleet-aggregated prefix_hits/prefix_entries, what the
        # gateway's affinity bench reads per cell
        server = _cell(bundle, replicas=2)
        try:
            for _ in range(3):
                assert server.generate((4, 4, 4), seed=2,
                                       timeout=90).ok
            st = server.stats()
            assert "prefix_hits" in st and "prefix_entries" in st
            assert st["prefix_entries"] >= 1
            assert st["prefix_hits"] >= 1
        finally:
            server.close()
