"""Overload control + failure isolation (reference
policy/auto_concurrency_limiter.cpp + circuit_breaker.cpp + the
fault-injection proof plane).

Three layers of proof:

- unit: the gradient limiter driven on a SYNTHETIC clock (every
  ``on_responded`` carries ``now_us``) — overload shrinks the limit,
  recovery raises it, all-fail windows halve it, the periodic probe-down
  remeasures the no-load floor; the breaker's EMA windows and exponential
  isolation; the injector's counter-based determinism.
- integration: a real server with ``max_concurrency="auto"`` sheds a 4x
  flood with ELIMIT while admitted p99 stays within 2x the unloaded
  baseline; a 3-backend round-robin channel isolates a browned-out
  backend within the breaker's short window and revives it after the
  fault clears — deterministic via FaultInjector, waits are bounded
  condition polls, never bare sleeps-as-synchronization.
- plumbing: adaptive limits pushed into the native plane
  (tb_server_set_native_max_concurrency), the /circuit_breakers page,
  the scrapeable gauges, device-link re-handshake backoff.
"""

from __future__ import annotations

import threading
import time

import pytest

from incubator_brpc_tpu.rpc import (
    Channel,
    ChannelOptions,
    Controller,
    FaultInjector,
    Server,
    ServerOptions,
    install_socket_injector,
)
from incubator_brpc_tpu.rpc.circuit_breaker import (
    CircuitBreaker,
    breaker_registry,
)
from incubator_brpc_tpu.rpc.concurrency_limiter import (
    AutoConcurrencyLimiter,
    ConstantConcurrencyLimiter,
    create_concurrency_limiter,
)
from incubator_brpc_tpu.utils.flags import flag_registry, set_flag_unchecked
from incubator_brpc_tpu.utils.status import ErrorCode


@pytest.fixture
def flags(tuned_flags):
    """Snapshot/restore any flag a test retunes — delegates to the shared
    ``tuned_flags`` fixture (conftest.py) so ONE implementation owns the
    restore discipline; kept under the historical local name."""
    yield tuned_flags


def wait_until(cond, timeout=10.0, interval=0.02):
    """Bounded condition poll (allowed: the condition is the
    synchronization; a bare sleep would not be)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


# ---------------------------------------------------------------------------
# unit: the gradient limiter on a synthetic clock
# ---------------------------------------------------------------------------


class TestAutoLimiterUnit:
    def _feed(self, lim, n, latency_us, interval_us, now):
        """n completions, one per interval (so qps == 1e6/interval)."""
        for _ in range(n):
            now += interval_us
            lim.on_responded(0, latency_us, now_us=now)
        return now

    def test_initial_limit_from_flag(self, flags):
        flags("auto_cl_initial_max_concurrency", 17)
        lim = AutoConcurrencyLimiter()
        assert lim.max_concurrency() == 17
        assert lim.on_requested(17)
        assert not lim.on_requested(18)

    def test_overload_shrinks_then_recovery_raises(self, flags):
        flags("auto_cl_sampling_interval_us", 0)
        flags("auto_cl_initial_max_concurrency", 40)
        # keep the probe-down out of this test's horizon
        flags("auto_cl_noload_latency_remeasure_interval_ms", 10**7)
        lim = AutoConcurrencyLimiter()
        now = 1_000_000
        # healthy: 10k qps at 1ms -> Little's law concurrency ~10
        now = self._feed(lim, 1500, 1000.0, 100, now)
        healthy = lim.max_concurrency()
        assert 10 <= healthy <= 14, lim.describe()
        # saturated brownout: latency 6x the floor, throughput collapses
        # to 2.5k qps -> the gradient walks the limit down toward ~3
        for _ in range(15):
            now = self._feed(lim, 250, 6000.0, 400, now)
        overloaded = lim.max_concurrency()
        assert overloaded < healthy, lim.describe()
        assert overloaded <= 6, lim.describe()
        # recovery: latency back at the floor, qps ceiling re-proven ->
        # the limit converges back up
        now = self._feed(lim, 1500, 1000.0, 100, now)
        recovered = lim.max_concurrency()
        assert recovered > overloaded, lim.describe()
        assert recovered >= 10, lim.describe()

    def test_all_fail_window_halves(self, flags):
        flags("auto_cl_sampling_interval_us", 0)
        flags("auto_cl_initial_max_concurrency", 32)
        lim = AutoConcurrencyLimiter()
        now = 1_000_000
        for _ in range(int(flag_registry.get("auto_cl_max_sample_count"))):
            now += 100
            lim.on_responded(ErrorCode.EINTERNAL, 1000.0, now_us=now)
        assert lim.max_concurrency() == 16

    def test_probe_down_remeasures_floor(self, flags):
        flags("auto_cl_sampling_interval_us", 0)
        flags("auto_cl_initial_max_concurrency", 40)
        flags("auto_cl_noload_latency_remeasure_interval_ms", 50)
        # min == max: every 100th sample settles a window exactly
        flags("auto_cl_min_sample_count", 100)
        flags("auto_cl_max_sample_count", 100)
        lim = AutoConcurrencyLimiter()
        now = 1_000_000
        now = self._feed(lim, 100, 1000.0, 100, now)
        settled = lim.max_concurrency()
        assert lim.describe()["min_latency_us"] > 0
        # cross the remeasure horizon: the next settled window probes down
        # to reduce_ratio of the limit and opens the 2-RTT drain window
        now += 60_000
        now = self._feed(lim, 100, 1000.0, 100, now)
        d = lim.describe()
        assert d["remeasuring"], d
        assert d["max_concurrency"] < settled, d
        # the drain passes: the floor resets and is re-measured fresh
        now += 10_000
        now = self._feed(lim, 201, 1000.0, 100, now)
        d2 = lim.describe()
        assert not d2["remeasuring"], d2
        assert d2["min_latency_us"] > 0

    def test_sampling_interval_thins_samples(self, flags):
        flags("auto_cl_sampling_interval_us", 1000)
        lim = AutoConcurrencyLimiter()
        # two completions inside one interval: only the first is taken
        lim.on_responded(0, 500.0, now_us=5_000_000)
        lim.on_responded(0, 500.0, now_us=5_000_100)
        assert lim._sw_succ == 1

    def test_create_limiter_specs(self):
        assert create_concurrency_limiter(0) is None
        assert create_concurrency_limiter(None) is None
        assert create_concurrency_limiter("constant") is None
        assert isinstance(
            create_concurrency_limiter(5), ConstantConcurrencyLimiter
        )
        assert isinstance(
            create_concurrency_limiter("auto"), AutoConcurrencyLimiter
        )
        assert create_concurrency_limiter("12").max_concurrency() == 12
        with pytest.raises(ValueError):
            create_concurrency_limiter("sideways")


# ---------------------------------------------------------------------------
# unit: breaker windows + injector determinism
# ---------------------------------------------------------------------------


class TestCircuitBreakerUnit:
    def test_initializing_phase_trips_on_error_count(self, flags):
        flags("circuit_breaker_short_window_size", 50)
        flags("circuit_breaker_short_window_error_percent", 10)
        flags("circuit_breaker_long_window_size", 1000)
        cb = CircuitBreaker()
        # the initializing budget is window * percent = 5 errors
        for _ in range(4):
            assert cb.on_call_end(ErrorCode.EINTERNAL, 1000.0)
        assert not cb.broken
        assert not cb.on_call_end(ErrorCode.EINTERNAL, 1000.0)
        assert cb.broken
        assert cb.isolated_times == 1

    def test_errors_within_budget_stay_closed(self, flags):
        flags("circuit_breaker_short_window_size", 100)
        flags("circuit_breaker_short_window_error_percent", 10)
        flags("circuit_breaker_long_window_size", 1000)
        cb = CircuitBreaker()
        # 5% errors through the whole initializing window: healthy
        for i in range(100):
            code = ErrorCode.EINTERNAL if i % 20 == 0 else 0
            assert cb.on_call_end(code, 1000.0)
        assert not cb.broken

    def test_isolation_duration_doubles_on_fast_retrip(self, flags):
        flags("circuit_breaker_short_window_size", 20)
        flags("circuit_breaker_min_isolation_duration_ms", 100)
        flags("circuit_breaker_max_isolation_duration_ms", 1000)
        cb = CircuitBreaker()
        for _ in range(3):
            cb.on_call_end(ErrorCode.EINTERNAL, 1000.0)
        assert cb.broken
        assert cb.isolation_duration_ms == 100
        cb.reset()  # half-open
        assert cb.state() == "half_open"
        for _ in range(3):
            cb.on_call_end(ErrorCode.EINTERNAL, 1000.0)
        assert cb.broken
        assert cb.isolation_duration_ms == 200  # doubled
        cb.reset()
        for _ in range(3):
            cb.on_call_end(ErrorCode.EINTERNAL, 1000.0)
        assert cb.isolation_duration_ms == 400

    def test_ema_error_cost_decays_on_success(self, flags):
        # window 100 @ 10%: a single error is far under the trip budget,
        # so the breaker stays closed and keeps feeding the recorders
        flags("circuit_breaker_short_window_size", 100)
        cb = CircuitBreaker()
        cb.on_call_end(0, 1000.0)
        cb.on_call_end(ErrorCode.EINTERNAL, 1000.0)
        cost1 = cb._short.describe()["ema_error_cost_us"]
        assert cost1 > 0
        for _ in range(50):
            assert cb.on_call_end(0, 1000.0)
        assert cb._short.describe()["ema_error_cost_us"] < cost1


class TestFaultInjectorUnit:
    def test_counter_schedule_is_deterministic_and_exact(self):
        inj = FaultInjector(error_rate=0.5)
        decisions = [inj.decide() for _ in range(100)]
        assert decisions.count("error") == 50
        # evenly interleaved, same positions every run
        inj2 = FaultInjector(error_rate=0.5)
        assert [inj2.decide() for _ in range(100)] == decisions

    def test_rates_compose(self):
        inj = FaultInjector(error_rate=0.25, delay_rate=0.25, delay_ms=0)
        decisions = [inj.decide() for _ in range(400)]
        assert decisions.count("error") == 100
        # delays only fire on operations the error schedule passed over
        assert 0 < decisions.count("delay") <= 100

    def test_close_takes_priority(self):
        inj = FaultInjector(error_rate=1.0, close_rate=1.0)
        assert inj.decide() == "close"


# ---------------------------------------------------------------------------
# integration: auto limiter on a live server
# ---------------------------------------------------------------------------


class TestServerAutoLimiter:
    def _start_capacity_server(self, capacity: int, work_s: float):
        """A server whose REAL capacity is ``capacity`` concurrent
        requests (a semaphore models the backend resource): admitted
        requests beyond it queue, so latency genuinely inflates when the
        limit overshoots — the world the gradient limiter regulates.
        Each handler records its own (monotonic, span_s, ahead) at the
        server, where over-admission queueing shows up: ``ahead`` is how
        many admitted requests it found inside the handler (at work or
        queued for the backend), which says how many rounds of the backend
        it waits out whatever the host's scheduling adds to ``span_s``."""
        sem = threading.Semaphore(capacity)
        spans = []
        span_lock = threading.Lock()
        inside = [0]

        def handler(cntl, req):
            t0 = time.perf_counter()
            with span_lock:
                ahead = inside[0]
                inside[0] += 1
            with sem:
                time.sleep(work_s)
            span = time.perf_counter() - t0
            with span_lock:
                inside[0] -= 1
                spans.append((time.monotonic(), span, ahead))
            return b"ok"

        srv = Server(ServerOptions(max_concurrency="auto"))
        srv.add_service("cap", {"work": handler})
        assert srv.start(0)
        return srv, spans

    @staticmethod
    def _p99(values):
        values = sorted(values)
        return values[int(len(values) * 0.99)]

    def test_flood_sheds_with_bounded_latency_then_converges(self, flags):
        flags("auto_cl_sampling_interval_us", 0)
        # windows: 10 samples settle one (baseline serial traffic at
        # ~19 qps settles in ~550ms), 20 cap a flood window
        flags("auto_cl_min_sample_count", 10)
        flags("auto_cl_max_sample_count", 20)
        flags("auto_cl_sample_window_size_ms", 2000)
        flags("auto_cl_initial_max_concurrency", 6)
        flags("auto_cl_noload_latency_remeasure_interval_ms", 3600 * 1000)
        # the qps ceiling decays toward the true (saturated) throughput
        # faster than the production default so a seconds-long test flood
        # reaches convergence, not just the direction of travel
        flags("auto_cl_qps_alpha_factor_for_ema", 0.3)
        flags("auto_cl_change_rate_of_explore_ratio", 0.06)
        # geometry constraints of this shared 1-core host: work_s must
        # dominate GIL scheduling noise (spans then measure queueing, the
        # thing the limiter regulates), and capacity + the initial limit
        # must sit BELOW the worker pool's ~8 handler slots, or the pool —
        # not the limiter — becomes the admission gate and nothing sheds
        capacity, work_s = 2, 0.05
        srv, spans = self._start_capacity_server(capacity, work_s)
        ch = Channel()
        assert ch.init(
            f"127.0.0.1:{srv.port}",
            options=ChannelOptions(timeout_ms=10000, max_retry=0),
        )
        try:
            # unloaded baseline: serial calls until the limiter's first
            # window has settled, which describe() reads under the
            # limiter's own lock. A window settles inside the sample that
            # fills it: the server counts a response after it has written
            # it, so the 20th sample can land after the client has its
            # answer, and on a loaded host a window that went stale before
            # its 10th sample starts over. No clock decides here: the next
            # call brings the next sample
            settled = False
            for sent in range(200):
                c = ch.call_method("cap", "work", b"")
                assert c.ok(), c.error_text
                settled = (
                    srv._server_limiter.describe()["min_latency_us"] > 0
                )
                if settled and sent >= 19:
                    break
            assert settled, (
                "baseline window never settled", srv._server_limiter.describe(),
            )
            p99_base = self._p99([s for _, s, _ in spans])
            assert max(ahead for _, _, ahead in spans) == 0  # serial: no queue
            limit_unloaded = srv.max_concurrency  # what serial calls hold it at
            spans.clear()

            # 4x overload flood (8 callers vs capacity 2): shed or melt
            codes = []
            code_lock = threading.Lock()
            flood_s = 6.0
            stop_at = time.monotonic() + flood_s

            def flood():
                while time.monotonic() < stop_at:
                    c = ch.call_method("cap", "work", b"")
                    if c.failed():
                        with code_lock:
                            codes.append(c.error_code)
                        time.sleep(0.02)  # a shed caller backs off a tick

            threads = [threading.Thread(target=flood) for _ in range(8)]
            t_start = time.monotonic()
            for t in threads:
                t.start()
            for t in threads:
                t.join()

            assert ErrorCode.ELIMIT in codes, (
                "flood was never shed",
                srv._server_limiter.describe(),
                len(spans),
            )
            # once the limiter has converged (last 30% of the flood), an
            # ADMITTED request waits out at most two rounds of the backend
            # (its own and one queued before it): the limit stopped
            # queueing from forming. Counted in requests found ahead, not
            # in seconds: two rounds are 100 ms against a p99 bound of 2x
            # the unloaded ~50.4 ms, which the host's scheduling decided
            # (149-166 ms read with two ahead at limit=3); the latencies
            # are reported beside it
            tail_from = t_start + flood_s * 0.7
            tail = [(s, ahead) for t, s, ahead in spans if t >= tail_from]
            assert tail, "no admitted requests in the flood tail"
            p99_tail = self._p99([s for s, _ in tail])
            assert max(ahead for _, ahead in tail) < 2 * capacity, (
                f"admitted p99 {p99_tail * 1e3:.1f}ms vs unloaded "
                f"{p99_base * 1e3:.1f}ms (limit={srv.max_concurrency})",
                sorted(ahead for _, ahead in tail)[-5:],
            )
            # the limit itself converged toward true capacity, below the
            # 6 it started from
            assert srv.max_concurrency <= capacity * 2, srv.max_concurrency
            after_flood = srv._server_limiter.describe()

            # the flood is gone: moderate healthy traffic is all admitted,
            # re-proves the floor, and the limiter explores upward again
            # (its explore ratio widens from where the flood left it) with
            # the limit no lower than such traffic held it before the
            # flood. Not "the limit the flood ended on": serial calls hold
            # 2, and a flood that ended on 3 failed that one run in twelve
            def limit_recovered():
                for _ in range(10):
                    c = ch.call_method("cap", "work", b"")
                    assert c.ok(), c.error_text
                now = srv._server_limiter.describe()
                return (
                    now["explore_ratio"] > after_flood["explore_ratio"]
                    and srv.max_concurrency >= limit_unloaded
                )

            assert after_flood["explore_ratio"] < flag_registry.get(
                "auto_cl_max_explore_ratio"
            )  # the flood had narrowed it: there is room to widen
            assert wait_until(limit_recovered, timeout=8.0), (
                after_flood, srv._server_limiter.describe(), limit_unloaded,
            )
        finally:
            srv.stop()
            srv.join(5)

    def test_constant_limit_still_works(self):
        srv = Server(ServerOptions(max_concurrency=1))
        gate = threading.Event()
        srv.add_service("s", {"m": lambda cntl, req: (gate.wait(5), b"")[1]})
        assert srv.start(0)
        try:
            ch = Channel()
            assert ch.init(
                f"127.0.0.1:{srv.port}",
                options=ChannelOptions(max_retry=0, timeout_ms=8000),
            )
            held = threading.Thread(
                target=lambda: ch.call_method("s", "m", b"")
            )
            held.start()
            assert wait_until(lambda: srv._nprocessing >= 1, 5.0)
            c = ch.call_method("s", "m", b"")
            gate.set()
            held.join(10)
            assert c.failed() and c.error_code == ErrorCode.ELIMIT
        finally:
            gate.set()
            srv.stop()
            srv.join(5)

    def test_runtime_reset_to_auto(self, flags):
        flags("auto_cl_initial_max_concurrency", 9)
        srv = Server()
        srv.add_service("s", {"m": lambda cntl, req: b""})
        assert srv.start(0)
        try:
            assert srv.max_concurrency == 0
            prev = srv.reset_max_concurrency("auto")
            assert prev == 0
            assert srv.max_concurrency == 9
            assert srv.reset_max_concurrency(25) == "auto"
            assert srv.max_concurrency == 25
        finally:
            srv.stop()
            srv.join(5)

    def test_per_method_auto_spec(self, flags):
        flags("auto_cl_initial_max_concurrency", 6)
        srv = Server()
        srv.add_service(
            "s", {"m": lambda cntl, req: b""}, max_concurrency="auto"
        )
        status = srv.method_status("s", "m")
        assert isinstance(status.limiter, AutoConcurrencyLimiter)
        assert status.max_concurrency == 6
        assert srv.set_method_max_concurrency("s.m", 3)
        assert status.max_concurrency == 3


@pytest.mark.skipif(
    not __import__(
        "incubator_brpc_tpu.transport.native_plane", fromlist=["NET_AVAILABLE"]
    ).NET_AVAILABLE,
    reason="native runtime unavailable",
)
class TestNativePlaneAdaptiveLimit:
    def test_adaptive_limit_reaches_native_dispatch(self, flags):
        from incubator_brpc_tpu.rpc import native_echo

        flags("auto_cl_sampling_interval_us", 0)
        flags("auto_cl_min_sample_count", 20)
        flags("auto_cl_max_sample_count", 40)
        flags("auto_cl_initial_max_concurrency", 16)
        srv = Server(
            ServerOptions(max_concurrency="auto", native_plane=True)
        )
        srv.add_service("svc", {"echo": native_echo})
        assert srv.start(0)
        try:
            plane = srv._native_plane
            assert plane is not None
            assert "svc.echo" in plane.native_method_names()
            # seeded at start with the initial adaptive limit
            assert plane.native_max_concurrency("svc.echo") == 16
            # drive the SERVER limiter with a synthetic overload (the
            # deterministic path) and watch the push reach the C++ table
            now = 1_000_000
            for _ in range(20):
                for _ in range(50):
                    now += 400
                    srv._server_limiter.on_responded(0, 6000.0, now_us=now)
            new_limit = srv.max_concurrency
            assert new_limit != 16, srv._server_limiter.describe()
            assert plane.native_max_concurrency("svc.echo") == new_limit
            # and the C++ dispatch path ENFORCES what was pushed: clamp to
            # 1, hold that slot with a slow Python-routed request? native
            # methods have no slow path — instead prove the limit value is
            # read per request by the existing ELIMIT machinery: set 0
            # (unlimited) and 1 and observe both accepted
            assert plane.set_native_max_concurrency("svc.echo", 1)
            assert plane.native_max_concurrency("svc.echo") == 1
            ch = Channel()
            assert ch.init(
                f"127.0.0.1:{srv.port}",
                options=ChannelOptions(native_plane=True),
            )
            c = ch.call_method("svc", "echo", b"x")
            assert c.ok(), c.error_text
        finally:
            srv.stop()
            srv.join(5)

    def test_numeric_string_limit_keeps_python_route(self):
        # "12" resolves to a CONSTANT limiter (same as 12): native-kind
        # methods must stay on the Python route where the server-wide
        # gate is enforced, exactly as with an int spec
        from incubator_brpc_tpu.rpc import native_echo

        srv = Server(ServerOptions(max_concurrency="12", native_plane=True))
        srv.add_service("svc", {"echo": native_echo})
        assert srv.start(0)
        try:
            assert srv._native_plane is not None
            assert srv._native_plane.native_method_names() == []
            assert srv.max_concurrency == 12
        finally:
            srv.stop()
            srv.join(5)

    def test_runtime_method_limit_stops_following_server_pushes(self, flags):
        # a per-method limit set at runtime must not be clobbered by the
        # next server-wide adaptive push on the C++ plane
        from incubator_brpc_tpu.rpc import native_echo

        flags("auto_cl_initial_max_concurrency", 8)
        srv = Server(ServerOptions(max_concurrency="auto", native_plane=True))
        srv.add_service("svc", {"echo": native_echo})
        assert srv.start(0)
        try:
            plane = srv._native_plane
            assert "svc.echo" in plane.auto_limit_targets()
            assert srv.set_method_max_concurrency("svc.echo", 5)
            assert plane.native_max_concurrency("svc.echo") == 5
            assert "svc.echo" not in plane.auto_limit_targets()
            srv._on_server_limit_change(80)  # a server-wide adaptive move
            assert plane.native_max_concurrency("svc.echo") == 5  # kept
            # clearing back to unlimited resumes following
            assert srv.set_method_max_concurrency("svc.echo", 0)
            assert "svc.echo" in plane.auto_limit_targets()
        finally:
            srv.stop()
            srv.join(5)

    def test_reset_away_from_auto_clears_native_ceiling(self, flags):
        from incubator_brpc_tpu.rpc import native_echo

        flags("auto_cl_initial_max_concurrency", 5)
        srv = Server(ServerOptions(max_concurrency="auto", native_plane=True))
        srv.add_service("svc", {"echo": native_echo})
        assert srv.start(0)
        try:
            plane = srv._native_plane
            assert plane.native_max_concurrency("svc.echo") == 5
            # operator disables limiting: the stale adaptive ceiling must
            # not keep shedding natively-dispatched requests
            srv.reset_max_concurrency(0)
            assert plane.native_max_concurrency("svc.echo") == 0
            # and back to auto re-seeds the fresh limiter's limit
            srv.reset_max_concurrency("auto")
            assert plane.native_max_concurrency("svc.echo") == 5
        finally:
            srv.stop()
            srv.join(5)


# ---------------------------------------------------------------------------
# integration: brownout recovery through the circuit breaker (acceptance)
# ---------------------------------------------------------------------------


class TestBrownoutRecovery:
    def _echo_server(self, options=None):
        srv = Server(options)
        hits = []
        srv.add_service(
            "e", {"m": lambda cntl, req: (hits.append(1), b"ok")[1]}
        )
        assert srv.start(0)
        return srv, hits

    def test_breaker_isolates_brownout_and_revives(self, flags):
        flags("circuit_breaker_short_window_size", 30)
        flags("circuit_breaker_long_window_size", 300)
        flags("circuit_breaker_min_isolation_duration_ms", 400)
        flags("fault_injection", True)
        flags("enable_circuit_breaker", True)
        servers = []
        ch = None
        try:
            a, hits_a = self._echo_server()
            b, hits_b = self._echo_server()
            # backend c browns out: 50% of its dispatches fail (injected,
            # deterministic — every 2nd request)
            c, hits_c = self._echo_server(
                ServerOptions(fault_injector=FaultInjector(error_rate=0.5))
            )
            servers = [a, b, c]
            url = "list://" + ",".join(
                f"127.0.0.1:{s.port}" for s in servers
            )
            ch = Channel()
            assert ch.init(
                url, lb_name="rr",
                options=ChannelOptions(max_retry=0, timeout_ms=4000),
            )
            lb = ch._lb

            # phase 1: drive calls until the breaker trips. The short
            # window (30 samples, 10%) must isolate c within its
            # initializing budget: 3 errors = 6 calls to c = ~18 total.
            fails_before = 0
            for i in range(120):
                if lb.isolated_servers():
                    break
                if ch.call_method("e", "m", b"x").failed():
                    fails_before += 1
            iso = lb.isolated_servers()
            assert len(iso) == 1 and iso[0].port == c.port, (
                iso, fails_before,
            )
            assert fails_before >= 3  # the trips that tripped it

            # phase 2: with c isolated, the channel's error rate returns
            # to <2% (here: zero) within the next short window of traffic
            window = 30
            fails_after = sum(
                1
                for _ in range(window)
                if ch.call_method("e", "m", b"x").failed()
            )
            assert fails_after / window < 0.02, fails_after
            assert lb.breaker_states()[f"127.0.0.1:{c.port}"][
                "state"
            ] == "isolated"

            # phase 3: the fault clears; after the isolation window the
            # node revives (half-open) and serves real traffic again
            c.fault_injector = None
            assert wait_until(
                lambda: not (
                    ch.call_method("e", "m", b"x") and lb.isolated_servers()
                ),
                timeout=10.0,
            )
            before_c = len(hits_c)
            fails_revived = 0
            for _ in range(60):
                if ch.call_method("e", "m", b"x").failed():
                    fails_revived += 1
            assert fails_revived == 0
            assert len(hits_c) > before_c, "revived backend got no traffic"
            state = lb.breaker_states()[f"127.0.0.1:{c.port}"]["state"]
            assert state in ("half_open", "closed"), state
        finally:
            if ch is not None and ch._lb is not None:
                ch._lb.stop()  # unregister breakers from the global registry
            for s in servers:
                s.stop()

    def test_all_isolated_is_ehostdown(self, flags):
        flags("circuit_breaker_short_window_size", 20)
        flags("circuit_breaker_min_isolation_duration_ms", 2000)
        flags("fault_injection", True)
        flags("enable_circuit_breaker", True)
        srv = Server(
            ServerOptions(fault_injector=FaultInjector(error_rate=1.0))
        )
        srv.add_service("e", {"m": lambda cntl, req: b"ok"})
        assert srv.start(0)
        try:
            ch = Channel()
            assert ch.init(
                f"list://127.0.0.1:{srv.port}", lb_name="rr",
                options=ChannelOptions(max_retry=0, timeout_ms=2000),
            )
            for _ in range(10):
                c = ch.call_method("e", "m", b"x")
                if ch._lb.isolated_servers():
                    break
            assert ch._lb.isolated_servers()
            c = ch.call_method("e", "m", b"x")
            assert c.failed() and c.error_code == ErrorCode.EHOSTDOWN, (
                c.error_code, c.error_text,
            )
            ch._lb.stop()  # unregister breakers from the global registry
        finally:
            srv.stop()

    def test_breaker_disabled_by_flag(self, flags):
        flags("fault_injection", True)
        flags("enable_circuit_breaker", False)
        flags("circuit_breaker_short_window_size", 10)
        srv = Server(
            ServerOptions(fault_injector=FaultInjector(error_rate=1.0))
        )
        srv.add_service("e", {"m": lambda cntl, req: b"ok"})
        assert srv.start(0)
        try:
            ch = Channel()
            assert ch.init(
                f"list://127.0.0.1:{srv.port}", lb_name="rr",
                options=ChannelOptions(max_retry=0, timeout_ms=2000),
            )
            for _ in range(30):
                ch.call_method("e", "m", b"x")
            assert not ch._lb.isolated_servers()
            ch._lb.stop()
        finally:
            srv.stop()

    def test_stragglers_do_not_reisolate_or_extend(self, flags):
        # completions landing AFTER the trip (the breaker reports
        # unhealthy for all of them) must not re-extend the isolation
        # deadline — only the trip transition isolates
        flags("circuit_breaker_short_window_size", 10)
        flags("circuit_breaker_min_isolation_duration_ms", 5000)
        flags("fault_injection", True)
        srv = Server(
            ServerOptions(fault_injector=FaultInjector(error_rate=1.0))
        )
        srv.add_service("e", {"m": lambda cntl, req: b"ok"})
        assert srv.start(0)
        try:
            ch = Channel()
            assert ch.init(
                f"list://127.0.0.1:{srv.port}", lb_name="rr",
                options=ChannelOptions(max_retry=0, timeout_ms=2000),
            )
            lb = ch._lb
            for _ in range(5):
                ch.call_method("e", "m", b"x")
            ep = lb.isolated_servers()[0]
            deadline = lb._isolated[ep]
            # straggler feedback on the already-broken breaker: the
            # deadline must not move
            sock = next(iter(lb._ep_by_sid))
            class FakeSock:
                id = sock
            lb.feedback(FakeSock(), 1000.0, ErrorCode.EINTERNAL)
            assert lb._isolated[ep] == deadline
            lb.stop()
        finally:
            srv.stop()

    def test_backup_superseded_original_spares_breaker(self, flags):
        # the backup-raced ORIGINAL attempt settles as EBACKUPREQUEST in
        # LB feedback: a healthy-but-slow node must not accrue error cost
        # from backup accounting
        flags("enable_circuit_breaker", True)
        flags("circuit_breaker_short_window_size", 10)
        slow_evt = threading.Event()

        def slow(cntl, req):
            slow_evt.wait(0.2)
            return b"slow"

        s1 = Server()
        s1.add_service("e", {"m": slow})
        assert s1.start(0)
        s2 = Server()
        s2.add_service("e", {"m": lambda cntl, req: b"fast"})
        assert s2.start(0)
        try:
            ch = Channel()
            assert ch.init(
                f"list://127.0.0.1:{s1.port},127.0.0.1:{s2.port}",
                lb_name="rr",
                options=ChannelOptions(
                    max_retry=1, timeout_ms=4000, backup_request_ms=20
                ),
            )
            for _ in range(12):
                c = ch.call_method("e", "m", b"x")
                assert c.ok(), c.error_text
            slow_evt.set()
            # the slow node was repeatedly backup-raced but never errored:
            # its breaker must hold zero error cost and stay closed
            states = ch._lb.breaker_states()
            row = states.get(f"127.0.0.1:{s1.port}")
            if row is not None:
                assert row["state"] == "closed", row
                assert row["short_window"]["errors"] == 0, row
            assert not ch._lb.isolated_servers()
            ch._lb.stop()
        finally:
            slow_evt.set()
            s1.stop()
            s2.stop()

    def test_connect_refused_feeds_breaker(self, flags):
        # a hard-down node (connect refused) is the most common failure
        # mode: it must accrue breaker error cost from the select path
        # and isolate, not stay in rotation burning a dial per pick
        import socket as pysocket

        flags("enable_circuit_breaker", True)
        flags("circuit_breaker_short_window_size", 20)
        flags("circuit_breaker_min_isolation_duration_ms", 5000)
        up = Server()
        up.add_service("e", {"m": lambda cntl, req: b"ok"})
        assert up.start(0)
        probe = pysocket.socket()
        probe.bind(("127.0.0.1", 0))
        dead_port = probe.getsockname()[1]
        probe.close()
        try:
            ch = Channel()
            assert ch.init(
                f"list://127.0.0.1:{up.port},127.0.0.1:{dead_port}",
                lb_name="rr",
                options=ChannelOptions(max_retry=1, timeout_ms=2000),
            )
            for _ in range(15):
                c = ch.call_method("e", "m", b"x")
                assert c.ok(), c.error_text
                if ch._lb.isolated_servers():
                    break
            iso = ch._lb.isolated_servers()
            assert iso and iso[0].port == dead_port, (
                iso, ch._lb.breaker_states(),
            )
            ch._lb.stop()
        finally:
            up.stop()

    def test_naming_churn_drops_breaker(self, flags):
        # a departed endpoint's breaker + registry row + isolation entry
        # go with it (autoscaling pools must not accumulate ghosts)
        flags("enable_circuit_breaker", True)
        srv = Server()
        srv.add_service("e", {"m": lambda cntl, req: b"ok"})
        assert srv.start(0)
        try:
            ch = Channel()
            assert ch.init(
                f"list://127.0.0.1:{srv.port}", lb_name="rr",
                options=ChannelOptions(max_retry=0, timeout_ms=2000),
            )
            assert ch.call_method("e", "m", b"x").ok()
            lb = ch._lb
            ep_key = f"127.0.0.1:{srv.port}"
            assert ep_key in lb.breaker_states()
            from incubator_brpc_tpu.utils.endpoint import EndPoint

            lb.remove_server(EndPoint(ip="127.0.0.1", port=srv.port))
            assert ep_key not in lb.breaker_states()
            assert not any(
                owner == lb._cb_tag
                for (owner, _), _cb in breaker_registry.snapshot()
            )
            lb.stop()
        finally:
            srv.stop()

    def test_lb_stop_unhooks_revival_callbacks(self, flags):
        # sockets are process-global and outlive channels: a stopped LB
        # must remove the on_revived closures it appended, or every
        # create/destroy channel cycle leaks one per endpoint
        flags("enable_circuit_breaker", True)
        srv = Server()
        srv.add_service("e", {"m": lambda cntl, req: b"ok"})
        assert srv.start(0)
        try:
            ch = Channel()
            assert ch.init(
                f"list://127.0.0.1:{srv.port}", lb_name="rr",
                options=ChannelOptions(max_retry=0, timeout_ms=2000),
            )
            assert ch.call_method("e", "m", b"x").ok()
            hooks = ch._lb._revival_hooks
            assert hooks, "revival hook was never installed"
            sock, cb = hooks[0]
            assert cb in sock.on_revived
            ch._lb.stop()
            assert cb not in sock.on_revived
            assert not ch._lb._revival_hooks
        finally:
            srv.stop()

    def test_extended_isolation_reschedules_revival_timer(self, flags):
        # straggler failures that EXTEND an isolation window must arm a
        # fresh timer: an idle channel would otherwise stay isolated
        # until its next select
        flags("circuit_breaker_short_window_size", 10)
        flags("circuit_breaker_min_isolation_duration_ms", 300)
        flags("fault_injection", True)
        srv = Server(
            ServerOptions(fault_injector=FaultInjector(error_rate=1.0))
        )
        srv.add_service("e", {"m": lambda cntl, req: b"ok"})
        assert srv.start(0)
        try:
            ch = Channel()
            assert ch.init(
                f"list://127.0.0.1:{srv.port}", lb_name="rr",
                options=ChannelOptions(max_retry=0, timeout_ms=2000),
            )
            lb = ch._lb
            for _ in range(5):
                ch.call_method("e", "m", b"x")
            assert lb.isolated_servers()
            ep = lb.isolated_servers()[0]
            # a straggler error arrives while isolated: the deadline
            # extends and a fresh timer must own it
            lb._isolate(ep)
            # no traffic at all from here on: revival must be TIMER-driven
            assert wait_until(
                lambda: ep not in lb._isolated, timeout=5.0
            ), lb._isolated
            lb.stop()
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# fault-injection seams + observability plumbing
# ---------------------------------------------------------------------------


class TestFaultSeams:
    def test_socket_write_seam(self, flags):
        flags("fault_injection", True)
        srv = Server()
        srv.add_service("e", {"m": lambda cntl, req: b"ok"})
        assert srv.start(0)
        try:
            ch = Channel()
            assert ch.init(
                f"127.0.0.1:{srv.port}",
                options=ChannelOptions(max_retry=0, timeout_ms=2000),
            )
            assert ch.call_method("e", "m", b"x").ok()
            install_socket_injector(FaultInjector(error_rate=1.0))
            try:
                c = ch.call_method("e", "m", b"x")
                assert c.failed(), "injected write error did not surface"
            finally:
                install_socket_injector(None)
            c = ch.call_method("e", "m", b"x")
            assert c.ok(), c.error_text
        finally:
            install_socket_injector(None)
            srv.stop()

    def test_master_flag_gates_everything(self, flags):
        flags("fault_injection", False)
        srv = Server(
            ServerOptions(fault_injector=FaultInjector(error_rate=1.0))
        )
        srv.add_service("e", {"m": lambda cntl, req: b"ok"})
        assert srv.start(0)
        try:
            install_socket_injector(FaultInjector(error_rate=1.0))
            try:
                ch = Channel()
                assert ch.init(
                    f"127.0.0.1:{srv.port}",
                    options=ChannelOptions(max_retry=0, timeout_ms=2000),
                )
                c = ch.call_method("e", "m", b"x")
                assert c.ok(), c.error_text  # both seams dormant
            finally:
                install_socket_injector(None)
        finally:
            srv.stop()

    def test_dispatch_delay_seam(self, flags):
        flags("fault_injection", True)
        inj = FaultInjector(delay_rate=1.0, delay_ms=30)
        srv = Server(ServerOptions(fault_injector=inj))
        srv.add_service("e", {"m": lambda cntl, req: b"ok"})
        assert srv.start(0)
        try:
            ch = Channel()
            assert ch.init(
                f"127.0.0.1:{srv.port}",
                options=ChannelOptions(max_retry=0, timeout_ms=4000),
            )
            t0 = time.perf_counter()
            c = ch.call_method("e", "m", b"x")
            dt = time.perf_counter() - t0
            assert c.ok() and dt >= 0.03, dt
            assert inj.injected["delay"] >= 1
        finally:
            srv.stop()


class TestObservability:
    def test_circuit_breakers_page_renders(self, flags):
        flags("fault_injection", True)
        flags("circuit_breaker_short_window_size", 10)
        flags("circuit_breaker_min_isolation_duration_ms", 5000)
        srv = Server(
            ServerOptions(fault_injector=FaultInjector(error_rate=1.0))
        )
        srv.add_service("e", {"m": lambda cntl, req: b"ok"})
        assert srv.start(0)
        try:
            ch = Channel()
            assert ch.init(
                f"list://127.0.0.1:{srv.port}", lb_name="rr",
                options=ChannelOptions(max_retry=0, timeout_ms=2000),
            )
            for _ in range(6):
                ch.call_method("e", "m", b"x")
            assert ch._lb.isolated_servers()

            from incubator_brpc_tpu.builtin import pages

            class Frame:
                path = "/circuit_breakers"
                query = {}

            status, ctype, body = pages.handle(None, Frame())
            text = body.decode()
            assert status == 200
            assert f"127.0.0.1:{srv.port}" in text
            assert "[isolated]" in text

            class JsonFrame:
                path = "/circuit_breakers"
                query = {"json": "1"}

            status, ctype, body = pages.handle(None, JsonFrame())
            assert status == 200 and ctype == "application/json"
            assert b"isolated" in body

            # the isolated-node gauge is scrapeable
            from incubator_brpc_tpu.builtin.prometheus import render_metrics

            metrics = render_metrics("circuit_breaker")
            assert "circuit_breaker_isolated_count 1" in metrics, metrics
            ch._lb.stop()
        finally:
            srv.stop()

    def test_auto_limit_gauge_scrapeable(self, flags):
        flags("auto_cl_initial_max_concurrency", 11)
        srv = Server(ServerOptions(max_concurrency="auto"))
        srv.add_service("e", {"m": lambda cntl, req: b"ok"})
        assert srv.start(0)
        try:
            from incubator_brpc_tpu.builtin.prometheus import render_metrics

            metrics = render_metrics(f"server_{srv.port}")
            assert f"server_{srv.port}_max_concurrency 11" in metrics, metrics
        finally:
            srv.stop()
            srv.join(5)
            # gauges hidden at stop: the name is free for the next server
            from incubator_brpc_tpu.builtin.prometheus import render_metrics

            assert (
                f"server_{srv.port}_max_concurrency"
                not in render_metrics(f"server_{srv.port}")
            )


class TestDeviceLinkBackoff:
    def test_rehandshake_backs_off_exponentially(self, flags):
        import socket as pysocket

        from incubator_brpc_tpu.transport.device_link import DeviceLinkMap

        flags("device_link_backoff_initial_ms", 200)
        flags("device_link_backoff_max_ms", 1000)
        # a port with NOTHING listening: the bootstrap dial fails fast
        probe = pysocket.socket()
        probe.bind(("127.0.0.1", 0))
        dead_port = probe.getsockname()[1]
        probe.close()
        from incubator_brpc_tpu.utils.endpoint import EndPoint

        dlm = DeviceLinkMap()
        ep = EndPoint(ip="127.0.0.1", port=dead_port)
        with pytest.raises((OSError, ConnectionError)):
            dlm.get_or_create(ep, timeout_ms=500)
        # the SECOND attempt inside the backoff window fails instantly
        # without dialing
        t0 = time.perf_counter()
        with pytest.raises(ConnectionError, match="backing off"):
            dlm.get_or_create(ep, timeout_ms=500)
        assert time.perf_counter() - t0 < 0.1
        key = next(iter(dlm._backoff))
        assert dlm._backoff[key][0] == 1
        # after the window, a real (failing) attempt doubles the backoff
        assert wait_until(
            lambda: time.monotonic() >= dlm._backoff[key][1], timeout=2.0
        )
        with pytest.raises((OSError, ConnectionError)):
            dlm.get_or_create(ep, timeout_ms=500)
        assert dlm._backoff[key][0] == 2


# ---------------------------------------------------------------------------
# Fabric-wide failure semantics (PR 8): deadline propagation, collective
# session abort/recovery, lame-duck drain
# ---------------------------------------------------------------------------


class _CaptureSock:
    """Duck-typed connection for driving Server.process_request directly:
    captures response bytes (materialized) in wire order."""

    def __init__(self):
        self.remote = None
        self.context = {}
        self.written = []

    def write(self, data, **kw):
        self.written.append(
            data.to_bytes() if hasattr(data, "to_bytes") else bytes(data)
        )
        return 0


class TestDeadlinePropagation:
    """The propagated deadline (tbus_std JSON meta / PRPC RpcRequestMeta
    field 8 ``timeout_ms``): servers shed expired work with EDEADLINE
    before dispatch; the budget decrements across hops."""

    def _shed_server(self):
        srv = Server()
        hits = []
        srv.add_service("S", {"m": lambda c, r: (hits.append(1), b"ok")[1]})
        assert srv.start(0)
        return srv, hits

    def test_expired_at_arrival_is_shed_without_dispatch(self):
        from incubator_brpc_tpu.protocol.tbus_std import (
            Meta,
            ParsedFrame,
            try_parse_frame,
        )
        from incubator_brpc_tpu.rpc.server import deadline_shed_count

        srv, hits = self._shed_server()
        try:
            sock = _CaptureSock()
            frame = ParsedFrame(
                meta=Meta(service="S", method="m", timeout_ms=50),
                payload=b"x",
                correlation_id=7,
            )
            frame.arrival_ts = time.monotonic() - 0.2  # 200 ms in queue
            before = deadline_shed_count.get_value()
            srv.process_request(sock, frame)
            assert not hits, "shed request must never invoke the method"
            resp, _ = try_parse_frame(sock.written[0])
            assert resp.error_code == ErrorCode.EDEADLINE
            assert resp.meta.error_text == "Deadline expired before dispatch"
            assert deadline_shed_count.get_value() == before + 1
        finally:
            srv.stop()
            srv.join(timeout=5)

    def test_unexpired_budget_dispatches_and_sets_deadline_left(self):
        from incubator_brpc_tpu.protocol.tbus_std import Meta, ParsedFrame

        srv = Server()
        seen = {}

        def handler(cntl, req):
            seen["left"] = cntl.deadline_left_ms()
            seen["timeout"] = cntl.timeout_ms
            return b"ok"

        srv.add_service("S", {"m": handler})
        assert srv.start(0)
        try:
            frame = ParsedFrame(
                meta=Meta(service="S", method="m", timeout_ms=5000),
                payload=b"x",
                correlation_id=8,
            )
            frame.arrival_ts = time.monotonic()
            srv.process_request(_CaptureSock(), frame)
            assert seen["timeout"] == 5000
            assert 0 < seen["left"] <= 5000
        finally:
            srv.stop()
            srv.join(timeout=5)

    def test_budget_decrements_across_hops(self):
        """edge -> A -> B: B sees strictly less budget than A stamped,
        shrunk by at least A's handler time — the Controller decrement."""
        seen = {}

        srv_b = Server()
        srv_b.add_service(
            "B",
            {
                "m": lambda c, r: (
                    seen.__setitem__(
                        "b_budget", c.request_meta.timeout_ms
                    ),
                    b"ok",
                )[1]
            },
        )
        assert srv_b.start(0)

        def a_handler(cntl, req):
            seen["a_budget"] = cntl.request_meta.timeout_ms
            time.sleep(0.12)  # burn budget before the downstream hop
            ch = Channel()
            assert ch.init(f"127.0.0.1:{srv_b.port}")
            # NOTE: no explicit timeout — the downstream call inherits
            # what is LEFT of the caller's propagated budget
            c2 = ch.call_method("B", "m", b"y")
            assert c2.ok(), c2.error_text
            return b"ok"

        srv_a = Server()
        srv_a.add_service("A", {"m": a_handler})
        assert srv_a.start(0)
        try:
            ch = Channel()
            assert ch.init(
                f"127.0.0.1:{srv_a.port}",
                options=ChannelOptions(timeout_ms=2000),
            )
            c = ch.call_method("A", "m", b"x")
            assert c.ok(), c.error_text
            assert 0 < seen["a_budget"] <= 2000
            assert seen["b_budget"] < seen["a_budget"] - 100, seen
        finally:
            srv_a.stop()
            srv_b.stop()
            srv_a.join(timeout=5)
            srv_b.join(timeout=5)

    def test_spent_budget_fails_fast_without_wire_traffic(self):
        from incubator_brpc_tpu.rpc import deadline as dl

        srv, hits = self._shed_server()
        try:
            ch = Channel()
            assert ch.init(f"127.0.0.1:{srv.port}")
            prev = dl.push_deadline(time.monotonic() - 0.01)
            try:
                c = ch.call_method("S", "m", b"x")
            finally:
                dl.pop_deadline(prev)
            assert c.error_code == ErrorCode.EDEADLINE
            assert not hits, "an expired budget must not reach the wire"
        finally:
            srv.stop()
            srv.join(timeout=5)


def _build_slow_native_lib(tmp_path):
    """Compile a tb_native_fn that sleeps 80 ms — the burst-delay that
    makes the SECOND frame of a batch expire mid-queue on the C++ plane.
    Skips when no C toolchain is available."""
    import subprocess

    src = tmp_path / "slow.c"
    src.write_text(
        "#include <stddef.h>\n"
        "#include <stdlib.h>\n"
        "#include <unistd.h>\n"
        "int tb_slow80(void* ud, const char* req, size_t n, char** resp,\n"
        "              size_t* resp_len) {\n"
        "    usleep(80000);\n"
        "    *resp = (char*)malloc(1);\n"
        "    (*resp)[0] = 's';\n"
        "    *resp_len = 1;\n"
        "    return 0;\n"
        "}\n"
    )
    so = tmp_path / "slow.so"
    try:
        subprocess.run(
            ["cc", "-shared", "-fPIC", "-O1", "-o", str(so), str(src)],
            check=True,
            capture_output=True,
            timeout=60,
        )
    except (OSError, subprocess.SubprocessError):
        pytest.skip("no C toolchain for the slow native method")
    return so


def _read_prpc_frames(sock, n):
    import struct as _struct

    out = []
    buf = b""
    for _ in range(n):
        while len(buf) < 12:
            buf += sock.recv(4096)
        body, _meta = _struct.unpack_from(">II", buf, 4)
        total = 12 + body
        while len(buf) < total:
            buf += sock.recv(4096)
        out.append(buf[:total])
        buf = buf[total:]
    return out


class TestNativeDeadlineShed:
    """The C++ cutter sheds expired-mid-queue work natively — EDEADLINE
    byte-identical to the Python route, counted and telemetry-recorded."""

    @pytest.fixture
    def native_shed(self, tmp_path):
        from incubator_brpc_tpu.transport import native_plane as np_mod

        if not np_mod.NET_AVAILABLE:
            pytest.skip("native plane unavailable")
        so = _build_slow_native_lib(tmp_path)
        srv = Server(ServerOptions(native_plane=True))
        slow = np_mod.native_method_lib(
            str(so), "tb_slow80", lambda c, r: b"s"
        )
        srv.add_service(
            "svc", {"slow": slow, "echo": np_mod.native_echo}
        )
        assert srv.start(0)
        if "svc.slow" not in srv._native_plane.native_method_names():
            srv.stop()
            pytest.skip("slow method did not register natively")
        yield srv
        srv.stop()
        srv.join(timeout=5)

    def test_native_shed_byte_identical_to_python_plane(self, native_shed):
        import socket as pysocket

        from incubator_brpc_tpu.protocol import baidu_std
        from incubator_brpc_tpu.protocol.tbus_std import Meta, ParsedFrame
        from incubator_brpc_tpu.rpc.server import deadline_shed_count

        srv = native_shed
        before = deadline_shed_count.get_value()
        # one burst: [slow (80 ms, no deadline), echo (30 ms budget)] —
        # the second frame expires while the first monopolizes the loop
        f1 = baidu_std.pack_request(
            Meta(service="svc", method="slow"), b"a", correlation_id=1
        )
        f2 = baidu_std.pack_request(
            Meta(service="svc", method="echo", timeout_ms=30),
            b"b",
            correlation_id=2,
        )
        with pysocket.create_connection(
            ("127.0.0.1", srv.port), timeout=10
        ) as s:
            s.sendall(f1 + f2)
            r1, r2 = _read_prpc_frames(s, 2)
        ok1, _ = baidu_std.try_parse_frame(r1)
        shed, _ = baidu_std.try_parse_frame(r2)
        assert ok1.error_code == 0
        assert shed.error_code == ErrorCode.EDEADLINE
        assert shed.meta.error_text == "Deadline expired before dispatch"

        # the Python plane's shed for the SAME request: byte-identical
        py_srv = Server()
        py_srv.add_service("svc", {"echo": lambda c, r: r})
        assert py_srv.start(0)
        try:
            cap = _CaptureSock()
            frame = ParsedFrame(
                meta=Meta(service="svc", method="echo", timeout_ms=30),
                payload=b"b",
                correlation_id=2,
            )
            frame.wire_protocol = "baidu_std"
            frame.arrival_ts = time.monotonic() - 0.08
            py_srv.process_request(cap, frame)
            assert cap.written[0] == r2, "native and Python sheds differ"
        finally:
            py_srv.stop()
            py_srv.join(timeout=5)

        # counted: the per-port C++ counter immediately; the global
        # deadline_shed_count once the telemetry drain folds it in
        assert srv._native_plane.stats()["deadline_sheds"] == 1
        srv._native_plane.drain_telemetry()
        assert deadline_shed_count.get_value() >= before + 1

    def test_fresh_deadline_rides_the_fast_path(self, native_shed):
        """A deadline-carrying frame with budget left stays on the
        interpreter-free plane (the scanner parses timeout_ms instead of
        routing to Python)."""
        srv = native_shed
        ch = Channel()
        assert ch.init(
            f"127.0.0.1:{srv.port}",
            options=ChannelOptions(
                native_plane=True, protocol="baidu_std", timeout_ms=2000
            ),
        )
        base = srv._native_plane.stats()
        c = ch.call_method("svc", "echo", b"hello")
        assert c.ok() and c.response_payload == b"hello"
        after = srv._native_plane.stats()
        assert after["native_reqs"] == base["native_reqs"] + 1
        assert after["cb_frames"] == base["cb_frames"]


class TestSessionAbortChaosDrill:
    """The acceptance chaos drill: kill one party mid multi-step session;
    survivors unblock with ESESSION within 2x the session deadline, the
    dead node's breaker trips, and a re-proposed session over the
    survivors succeeds."""

    DEADLINE_MS = 4000

    @pytest.fixture
    def mesh(self, tuned_flags):
        import jax

        # breaker windows sized so the dead party's refused dials trip it
        # within a screenful of calls (the TestBrownoutRecovery tuning)
        tuned_flags("circuit_breaker_short_window_size", 30)
        tuned_flags("circuit_breaker_long_window_size", 300)
        tuned_flags("circuit_breaker_min_isolation_duration_ms", 60000)
        tuned_flags("enable_circuit_breaker", True)
        from incubator_brpc_tpu.rpc import device_method
        from incubator_brpc_tpu.rpc.device_method import (
            DeviceMethod,
            lookup_device_method,
            register_device_method,
        )
        from incubator_brpc_tpu.transport.mc_worker import (
            SESSION_WIDTH,
            _scale_psum_kernel,
        )

        prev = lookup_device_method("dsvc", "scale")
        register_device_method(
            "dsvc", "scale", DeviceMethod(_scale_psum_kernel, width=SESSION_WIDTH)
        )
        servers, channels = [], []
        for i in range(3):
            s = Server(
                ServerOptions(
                    device_index=i + 1,
                    enable_collective_service=True,
                    collective_max_concurrency=0,
                )
            )
            s.add_service(
                "dsvc",
                {"scale": device_method(_scale_psum_kernel, width=SESSION_WIDTH)},
            )
            assert s.start(0)
            servers.append(s)
            ch = Channel()
            # every party behind its own breaker-owning LB (list:// =
            # LoadBalancerWithNaming), so the drill can prove WHO gets
            # charged for the death
            assert ch.init(
                f"list://127.0.0.1:{s.port}",
                lb_name="rr",
                options=ChannelOptions(max_retry=1, timeout_ms=8000),
            )
            channels.append(ch)
        party_ids = [d.id for d in jax.devices()[1:4]]
        yield servers, channels, party_ids
        from incubator_brpc_tpu.parallel import mc_dispatch

        mc_dispatch.set_step_hook(None)
        for ch in channels:
            if ch._lb is not None:
                ch._lb.stop()
        for s in servers:
            s.stop()
            s.join(timeout=5)

    def test_party_death_aborts_survivors_and_recovery_succeeds(self, mesh):
        from incubator_brpc_tpu.parallel import mc_dispatch

        servers, channels, party_ids = mesh
        operands = [bytes([i + 1]) * 8 for i in range(3)]
        before_aborts = mc_dispatch.dispatch_aborts.get_value()

        # park every party between steps so the kill lands MID-session
        mc_dispatch.set_step_hook(lambda step: time.sleep(0.03))
        killer = threading.Timer(
            0.4, lambda: (servers[0].stop(), servers[0].join(timeout=3))
        )
        killer.start()
        t0 = time.monotonic()
        try:
            with pytest.raises(mc_dispatch.SessionAborted) as exc:
                mc_dispatch.propose_dispatch(
                    channels,
                    party_ids,
                    "dsvc",
                    "scale",
                    operands,
                    steps=120,
                    proposer_index=None,
                    timeout_ms=30000,
                    session_deadline_ms=self.DEADLINE_MS,
                )
        finally:
            killer.cancel()
        elapsed = time.monotonic() - t0
        assert elapsed < 2 * self.DEADLINE_MS / 1000.0
        assert exc.value.dead_indexes == (0,)
        assert exc.value.survivor_indexes == (1, 2)
        assert exc.value.error_code == ErrorCode.ESESSION

        # every survivor's handler unblocked (returned ESESSION) within
        # 2x the session deadline — not wedged in the lockstep barrier
        deadline = t0 + 2 * self.DEADLINE_MS / 1000.0
        assert wait_until(
            lambda: servers[1]._nprocessing == 0
            and servers[2]._nprocessing == 0
            and mc_dispatch.active_sessions() == 0,
            timeout=max(0.1, deadline - time.monotonic()),
        )
        assert mc_dispatch.dispatch_aborts.get_value() > before_aborts
        mc_dispatch.set_step_hook(None)

        # the dead node's breaker trips (connect-refused selects feed it);
        # the survivors' breakers stay closed — their ESESSION answers are
        # excluded from error cost
        for _ in range(30):
            if channels[0]._lb.isolated_servers():
                break
            channels[0].call_method("dsvc", "scale", b"x")
        assert channels[0]._lb.isolated_servers(), (
            "dead party's breaker never tripped"
        )
        for i in (1, 2):
            assert not channels[i]._lb.isolated_servers(), (
                f"survivor {i}'s breaker tripped off cooperative aborts"
            )

        # recovery: the next session over the surviving set completes
        out = mc_dispatch.propose_dispatch(
            channels[1:],
            party_ids[1:],
            "dsvc",
            "scale",
            operands[1:],
            steps=2,
            proposer_index=None,
            timeout_ms=30000,
        )
        assert out["final_steps"] == 2
        assert all(r is not None for r in out["results"])

    def test_propose_with_recovery_drops_dead_party(self, mesh):
        from incubator_brpc_tpu.parallel import mc_dispatch

        servers, channels, party_ids = mesh
        operands = [bytes([i + 1]) * 8 for i in range(3)]
        mc_dispatch.set_step_hook(lambda step: time.sleep(0.03))
        killer = threading.Timer(
            0.3, lambda: (servers[0].stop(), servers[0].join(timeout=3))
        )
        killer.start()
        try:
            out = mc_dispatch.propose_with_recovery(
                channels,
                party_ids,
                "dsvc",
                "scale",
                operands,
                steps=40,
                proposer_index=None,
                timeout_ms=30000,
                session_deadline_ms=self.DEADLINE_MS,
            )
        finally:
            killer.cancel()
            mc_dispatch.set_step_hook(None)
        # the re-proposed session ran over the survivors only
        assert out["dead_party_ids"] == [party_ids[0]]
        assert out["final_steps"] == 40
        assert out["results"][0] is not None and out["results"][1] is not None

    def test_esession_excluded_from_breaker_cost(self, tuned_flags):
        """Unit: N ESESSION completions never charge a node's breaker;
        the same N EFAILEDSOCKET completions trip it."""
        tuned_flags("circuit_breaker_short_window_size", 10)
        tuned_flags("enable_circuit_breaker", True)
        srv = Server()
        srv.add_service("e", {"m": lambda c, r: b"ok"})
        assert srv.start(0)
        ch = Channel()
        assert ch.init(
            f"list://127.0.0.1:{srv.port}",
            lb_name="rr",
            options=ChannelOptions(max_retry=0, timeout_ms=2000),
        )
        try:
            lb = ch._lb
            assert ch.call_method("e", "m", b"x").ok()
            sock = lb.select_server()
            for _ in range(50):
                lb.feedback(sock, 1000.0, ErrorCode.ESESSION)
                lb.feedback(sock, 1000.0, ErrorCode.EDEADLINE)
            assert not lb.isolated_servers(), (
                "cooperative failure codes charged the breaker"
            )
            for _ in range(50):
                lb.feedback(sock, 1000.0, ErrorCode.EFAILEDSOCKET)
                if lb.isolated_servers():
                    break
            assert lb.isolated_servers(), "real errors must still trip it"
        finally:
            if ch._lb is not None:
                ch._lb.stop()
            srv.stop()
            srv.join(timeout=5)


class TestLameDuck:
    """enter_lame_duck / /quitquitquit: accepting stops, /health flips,
    in-flight work drains with zero connection resets, then hard stop."""

    def test_drains_inflight_flood_cleanly(self):
        srv = Server()
        srv.add_service(
            "S", {"slow": lambda c, r: (time.sleep(0.25), b"done")[1]}
        )
        assert srv.start(0)
        ch = Channel()
        assert ch.init(
            f"127.0.0.1:{srv.port}", options=ChannelOptions(timeout_ms=8000)
        )
        results = []
        lock = threading.Lock()

        def call():
            c = ch.call("S", "slow", b"x")
            with lock:
                results.append(c.error_code)

        ts = [threading.Thread(target=call) for _ in range(6)]
        for t in ts:
            t.start()
        # all six admitted: on a loaded host a straggler that arrives
        # after the drain starts is refused ELOGOFF like any new work
        assert wait_until(lambda: srv._nprocessing == 6, timeout=5.0)

        drain = srv.enter_lame_duck(grace_s=10)
        assert drain is not None
        assert srv.lame_duck

        # /health flips immediately
        from incubator_brpc_tpu.builtin.pages import _health

        class F:
            query = {}
            path = "/health"

        assert _health(srv, F)[0] == 503

        # NEW work is refused with (retriable) ELOGOFF, never a reset
        c2 = ch.call("S", "slow", b"y")
        assert c2.error_code == ErrorCode.ELOGOFF

        for t in ts:
            t.join()
        drain.join(timeout=15)
        assert not drain.is_alive()
        # zero connection-reset errors: every in-flight call completed OK
        assert results and all(code == 0 for code in results), results
        assert srv._stopping

    def test_quitquitquit_page_triggers_drain(self, flags):
        from incubator_brpc_tpu.builtin.pages import _quitquitquit

        flags("enable_quitquitquit", True)
        srv = Server()
        srv.add_service("S", {"m": lambda c, r: b"ok"})
        assert srv.start(0)

        class F:
            query = {"grace_s": "5"}
            path = "/quitquitquit"

        status, _ct, body = _quitquitquit(srv, F)
        assert status == 200 and b"lame-duck" in body
        assert srv.lame_duck
        srv._lame_duck_thread.join(timeout=10)
        assert srv._stopping

        class Bad:
            query = {"grace_s": "-1"}
            path = "/quitquitquit"

        assert _quitquitquit(srv, Bad)[0] == 400

    def test_quitquitquit_gated_off_by_default(self):
        """An unauthenticated remote stop must be opt-in (the /dir
        discipline): with the flag at its default the page refuses."""
        from incubator_brpc_tpu.builtin.pages import _quitquitquit

        srv = Server()
        srv.add_service("S", {"m": lambda c, r: b"ok"})
        assert srv.start(0)
        try:
            class F:
                query = {}
                path = "/quitquitquit"

            status, _ct, body = _quitquitquit(srv, F)
            assert status == 403 and b"enable_quitquitquit" in body
            assert not srv.lame_duck
        finally:
            srv.stop()
            srv.join(timeout=5)

    def test_lame_duck_drill_tool(self, flags):
        """The one-command drain-under-load run: rpc_press
        --lame-duck-drill against a live server reports a clean drain."""
        import sys

        flags("enable_quitquitquit", True)
        sys.path.insert(0, ".")
        from tools.rpc_press import run_lame_duck_drill

        srv = Server()
        srv.add_service("S", {"echo": lambda c, r: r})
        assert srv.start(0)
        counts = run_lame_duck_drill(
            f"127.0.0.1:{srv.port}",
            "S",
            "echo",
            b"x" * 32,
            threads=3,
            duration=2.0,
            timeout_ms=3000,
        )
        assert counts["drained_clean"], counts
        assert counts["ok"] > 0
        assert counts["reset"] == 0
        assert srv._stopping  # the drill terminated the target

    def test_sigterm_flag_installs_handler(self, tuned_flags):
        import signal

        from incubator_brpc_tpu.rpc import server as server_mod

        tuned_flags("graceful_quit_on_sigterm", True)
        prev_state = dict(server_mod._sigterm_state)
        prev_handler = signal.getsignal(signal.SIGTERM)
        server_mod._sigterm_state["installed"] = False
        try:
            srv = Server()
            srv.add_service("S", {"m": lambda c, r: b"ok"})
            assert srv.start(0)
            assert signal.getsignal(signal.SIGTERM) is server_mod._on_sigterm
            srv.stop()
            srv.join(timeout=5)
        finally:
            signal.signal(signal.SIGTERM, prev_handler)
            server_mod._sigterm_state.update(prev_state)


class TestNativeIdleReap:
    def test_idle_native_connection_reaped(self):
        """idle_timeout_s is enforced on native-plane ports now: an idle
        connection is culled from the C++ loops (satellite — the old
        behavior was a warning and an immortal connection)."""
        import socket as pysocket

        from incubator_brpc_tpu.transport import native_plane as np_mod

        if not np_mod.NET_AVAILABLE:
            pytest.skip("native plane unavailable")
        srv = Server(
            ServerOptions(native_plane=True, idle_timeout_s=0.4)
        )
        srv.add_service("svc", {"echo": np_mod.native_echo})
        assert srv.start(0)
        try:
            from incubator_brpc_tpu.protocol import baidu_std
            from incubator_brpc_tpu.protocol.tbus_std import Meta

            s = pysocket.create_connection(("127.0.0.1", srv.port), timeout=10)
            s.sendall(
                baidu_std.pack_request(
                    Meta(service="svc", method="echo"), b"hi", correlation_id=1
                )
            )
            (r1,) = _read_prpc_frames(s, 1)
            frame, _ = baidu_std.try_parse_frame(r1)
            assert frame.error_code == 0
            # now idle: the reap (scan at idle/2) must close it within a
            # few scan periods — recv returns b"" on the culled fd
            s.settimeout(5.0)
            got = s.recv(1)
            assert got == b"", "idle native connection was not reaped"
            s.close()
        finally:
            srv.stop()
            srv.join(timeout=5)


class TestNativeFaultSeam:
    """tb_channel_set_fault: the counter-scheduled client fault seam on
    the C++ plane (rpc_press --native-plane --fault-rate no longer forces
    the Python route)."""

    def test_deterministic_fail_schedule(self, flags):
        from incubator_brpc_tpu.transport import native_plane as np_mod

        if not np_mod.NET_AVAILABLE:
            pytest.skip("native plane unavailable")
        flags("fault_injection", True)
        np_mod.install_native_client_fault(fail_every=4)
        srv = Server(ServerOptions(native_plane=True))
        srv.add_service("svc", {"echo": np_mod.native_echo})
        assert srv.start(0)
        nch = None
        try:
            nch = np_mod.NativeClientChannel("127.0.0.1", srv.port)
            codes = []
            for _ in range(12):
                _rc, ec, _m, _b = nch.call(
                    "svc", "echo", b"x", timeout_ms=2000
                )
                codes.append(ec)
            # exact-rate counter schedule: every 4th call, same every run
            assert [i for i, ec in enumerate(codes) if ec] == [3, 7, 11]
            assert all(
                ec == ErrorCode.EINTERNAL for ec in codes if ec
            )
        finally:
            np_mod.install_native_client_fault()  # clear
            if nch is not None:
                nch.close()
            srv.stop()
            srv.join(timeout=5)

    def test_master_flag_gates_arming(self, flags):
        from incubator_brpc_tpu.transport import native_plane as np_mod

        if not np_mod.NET_AVAILABLE:
            pytest.skip("native plane unavailable")
        flags("fault_injection", False)  # master flag OFF
        np_mod.install_native_client_fault(fail_every=2)
        srv = Server(ServerOptions(native_plane=True))
        srv.add_service("svc", {"echo": np_mod.native_echo})
        assert srv.start(0)
        nch = None
        try:
            nch = np_mod.NativeClientChannel("127.0.0.1", srv.port)
            for _ in range(6):
                _rc, ec, _m, _b = nch.call(
                    "svc", "echo", b"x", timeout_ms=2000
                )
                assert ec == 0  # nothing injected without the master flag
        finally:
            np_mod.install_native_client_fault()
            if nch is not None:
                nch.close()
            srv.stop()
            srv.join(timeout=5)


# ---------------------------------------------------------------------------
# elastic collective sessions: checkpoint/resume, replacement, watchdog
# ---------------------------------------------------------------------------


class TestResumePointJoin:
    """The resume barrier's min-join (parallel/mc_dispatch.resume_point):
    last COMMON checkpointed step over the survivors — pure units."""

    def _info(self, wm, steps):
        return {"watermark": wm, "steps": list(steps)}

    def test_min_join_over_skewed_watermarks(self):
        from incubator_brpc_tpu.parallel.mc_dispatch import resume_point

        wms = {
            0: self._info(4, [2, 4]),
            1: self._info(6, [2, 4, 6]),
            2: self._info(2, [2]),
        }
        assert resume_point(wms) == 2

    def test_zero_checkpoint_falls_back_to_full_restart(self):
        from incubator_brpc_tpu.parallel.mc_dispatch import resume_point

        # one survivor never checkpointed: the whole join is 0 — restart
        assert resume_point(
            {0: self._info(6, [2, 4, 6]), 1: self._info(0, [])}
        ) == 0
        # a survivor that answered nothing at all drags to 0 too
        assert resume_point({0: self._info(6, [2, 4, 6]), 1: None}) == 0
        assert resume_point({}) == 0

    def test_evicted_min_falls_back_to_deepest_common(self):
        from incubator_brpc_tpu.parallel.mc_dispatch import resume_point

        # min watermark 4 was EVICTED from survivor 1's ring: fall back
        # to the deepest step everyone still retains
        wms = {
            0: self._info(4, [2, 4]),
            1: self._info(6, [2, 6]),
        }
        assert resume_point(wms) == 2
        # nothing common at all: full restart
        assert resume_point(
            {0: self._info(4, [4]), 1: self._info(6, [6])}
        ) == 0


class TestCheckpointRings:
    """Ring census/release/eviction units (no device traffic — the ring
    only retains references; materialization is a resume-path affair)."""

    def test_census_release_and_gauge(self):
        from incubator_brpc_tpu.parallel import mc_dispatch as mcd

        sid = "t-ring-census"
        ring = mcd._checkpoint_ring(sid, 1, (10, 11, 12), entry_bytes=100)
        for step in (2, 4, 6):
            ring.put(step, object(), object(), depth=2)
        # depth=2: step 2 evicted, watermark = newest retained
        assert ring.steps() == [4, 6]
        wm = mcd.checkpoint_watermarks(sid)
        assert wm[1]["watermark"] == 6 and wm[1]["steps"] == [4, 6]
        assert mcd.checkpoint_bytes_retained() >= 200
        assert mcd.release_checkpoints(sid)
        assert not mcd.checkpoint_watermarks(sid)
        assert not mcd.release_checkpoints(sid)  # idempotent

    def test_unready_entries_excluded_from_census(self):
        from incubator_brpc_tpu.parallel import mc_dispatch as mcd

        class _Arr:
            def __init__(self, ready):
                self._r = ready

            def is_ready(self):
                return self._r

        sid = "t-ring-ready"
        ring = mcd._checkpoint_ring(sid, 0, (1, 2), entry_bytes=10)
        ring.put(2, _Arr(True), _Arr(True), depth=4)
        ring.put(4, _Arr(False), _Arr(True), depth=4)
        # a dispatched-but-never-completed step (wedged behind a dead
        # party's collective) must not be elected as the resume point
        assert ring.steps() == [2]
        assert mcd.checkpoint_watermarks(sid)[0]["watermark"] == 2
        mcd.release_checkpoints(sid)

    def test_resumed_replay_replaces_stale_same_step_entry(self):
        """A resumed run re-checkpoints step numbers the aborted run
        already put(): the fresh entry must REPLACE the stale one (which
        may be wedged and never-ready), not shadow behind it."""
        from incubator_brpc_tpu.parallel import mc_dispatch as mcd

        class _Arr:
            def __init__(self, ready):
                self._r = ready

            def is_ready(self):
                return self._r

        sid = "t-ring-replace"
        ring = mcd._checkpoint_ring(sid, 0, (1, 2), entry_bytes=10)
        stale = _Arr(False)
        ring.put(2, _Arr(True), _Arr(True), depth=4)
        ring.put(4, stale, _Arr(True), depth=4)  # wedged, never ready
        assert ring.watermark() == 2
        fresh = _Arr(True)
        ring.put(4, fresh, _Arr(True), depth=4)  # the replayed step 4
        assert ring.steps() == [2, 4]
        assert ring.get(4)[0] is fresh  # not the stale shadow
        assert ring.watermark() == 4
        mcd.release_checkpoints(sid)

    def test_cap_eviction_spares_active_sessions(self):
        """Ring eviction prefers sessions with no live registrant: short
        -session churn must not strip a long-running session of the
        checkpoints its resume depends on."""
        from incubator_brpc_tpu.parallel import mc_dispatch as mcd

        sid = "t-ring-active"
        st = mcd._register_session(sid, (1, 2), deadline=0.0)
        try:
            mcd._checkpoint_ring(sid, 0, (1, 2), entry_bytes=1)
            for i in range(mcd._MAX_CHECKPOINT_SESSIONS + 4):
                mcd._checkpoint_ring(f"t-ring-churn-{i}", 0, (1,), entry_bytes=1)
            assert mcd._checkpoint_lookup(sid, 0) is not None, (
                "churn evicted a LIVE session's ring"
            )
        finally:
            mcd._unregister_session(st)
            mcd.release_checkpoints(sid)
            for i in range(mcd._MAX_CHECKPOINT_SESSIONS + 4):
                mcd.release_checkpoints(f"t-ring-churn-{i}")

    def test_session_cap_evicts_oldest(self):
        from incubator_brpc_tpu.parallel import mc_dispatch as mcd

        sids = [f"t-ring-cap-{i}" for i in range(mcd._MAX_CHECKPOINT_SESSIONS + 2)]
        for sid in sids:
            mcd._checkpoint_ring(sid, 0, (1,), entry_bytes=1)
        assert not mcd.checkpoint_watermarks(sids[0])  # evicted
        assert mcd._checkpoint_lookup(sids[-1], 0) is not None
        for sid in sids:
            mcd.release_checkpoints(sid)


class TestElasticSessionUnits:
    """run_dispatch_session's checkpoint/restore seam, driven directly
    (single process, all shards addressable)."""

    def test_resume_replays_only_steps_past_checkpoint(self):
        import jax

        devices = jax.devices()
        from incubator_brpc_tpu.parallel import mc_dispatch as mcd
        from incubator_brpc_tpu.rpc.device_method import DeviceMethod
        from incubator_brpc_tpu.transport.mc_worker import (
            SESSION_WIDTH,
            _scale_psum_kernel,
            session_expected,
        )

        pids = [d.id for d in devices[:3]]
        ops = [bytes([i + 1]) * 16 for i in range(3)]
        dm = DeviceMethod(_scale_psum_kernel, width=SESSION_WIDTH)
        sid = "t-unit-resume"
        try:
            mcd.run_dispatch_session(
                pids, 0, dm, ops, 6, session_id=sid, checkpoint_every=2
            )
            assert mcd.checkpoint_watermarks(sid)[0]["watermark"] == 6
            before = mcd.dispatch_steps.get_value()
            row, n, _el = mcd.run_dispatch_session(
                pids, 0, dm, ops, 12, session_id=sid, resume_from=6,
                checkpoint_every=2,
            )
            # only the steps past the checkpoint re-ran
            assert mcd.dispatch_steps.get_value() - before == 6
            # and the result is byte-identical to an undisturbed 12-step run
            assert dm.unpack(row, n) == session_expected(ops, 12)[0]
        finally:
            mcd.release_checkpoints(sid)

    def test_resume_point_equal_to_final_replays_nothing(self):
        import jax

        devices = jax.devices()
        from incubator_brpc_tpu.parallel import mc_dispatch as mcd
        from incubator_brpc_tpu.rpc.device_method import DeviceMethod
        from incubator_brpc_tpu.transport.mc_worker import (
            SESSION_WIDTH,
            _scale_psum_kernel,
            session_expected,
        )

        pids = [d.id for d in devices[:3]]
        ops = [b"\x05" * 8, b"\x06" * 8, b"\x07" * 8]
        dm = DeviceMethod(_scale_psum_kernel, width=SESSION_WIDTH)
        sid = "t-unit-resume-final"
        try:
            mcd.run_dispatch_session(
                pids, 0, dm, ops, 4, session_id=sid, checkpoint_every=2
            )
            before = mcd.dispatch_steps.get_value()
            row, n, _el = mcd.run_dispatch_session(
                pids, 0, dm, ops, 4, session_id=sid, resume_from=4,
            )
            assert mcd.dispatch_steps.get_value() - before == 0
            assert dm.unpack(row, n) == session_expected(ops, 4)[0]
        finally:
            mcd.release_checkpoints(sid)

    def test_replacement_reshard_round_trip(self):
        """The reshard wire format: checkpoint_fetch's b64 rows restore a
        party with NO local ring (the replacement's bootstrap) and the
        replayed chain lands byte-identical."""
        import jax

        devices = jax.devices()
        import base64

        from incubator_brpc_tpu.parallel import mc_dispatch as mcd
        from incubator_brpc_tpu.rpc.device_method import DeviceMethod
        from incubator_brpc_tpu.transport.mc_worker import (
            SESSION_WIDTH,
            _scale_psum_kernel,
            session_expected,
        )

        pids = [d.id for d in devices[:3]]
        ops = [bytes([7 * i + 1]) * 12 for i in range(3)]
        dm = DeviceMethod(_scale_psum_kernel, width=SESSION_WIDTH)
        sid = "t-unit-reshard"
        try:
            mcd.run_dispatch_session(
                pids, 0, dm, ops, 4, session_id=sid, checkpoint_every=2
            )
            rows = mcd.checkpoint_fetch(sid, 4, [0, 1, 2])
            assert set(rows) == {0, 1, 2}
            state = {
                i: (base64.b64decode(v["row"]), int(v["n"]))
                for i, v in rows.items()
            }
            assert all(len(r) == SESSION_WIDTH for r, _n in state.values())
            # a DIFFERENT device takes slot 0, restoring purely from the
            # resharded bytes under a session id with no local ring
            new_pids = [devices[3].id] + pids[1:]
            row, n, _el = mcd.run_dispatch_session(
                new_pids, 1, dm, ops, 8, session_id="t-unit-reshard-2",
                resume_from=4, resume_state=state,
            )
            assert dm.unpack(row, n) == session_expected(ops, 8)[1]
        finally:
            mcd.release_checkpoints(sid)
            mcd.release_checkpoints("t-unit-reshard-2")

    def test_missing_checkpoint_raises_lookup_error(self):
        import jax

        devices = jax.devices()
        from incubator_brpc_tpu.parallel import mc_dispatch as mcd
        from incubator_brpc_tpu.rpc.device_method import DeviceMethod
        from incubator_brpc_tpu.transport.mc_worker import (
            SESSION_WIDTH,
            _scale_psum_kernel,
        )

        pids = [d.id for d in devices[:3]]
        ops = [b"\x01" * 8] * 3
        dm = DeviceMethod(_scale_psum_kernel, width=SESSION_WIDTH)
        with pytest.raises(LookupError):
            mcd.run_dispatch_session(
                pids, 0, dm, ops, 8, session_id="t-no-such-ring",
                resume_from=4,
            )


class TestElasticResumeChaosDrill:
    """The acceptance drill: kill 1 of 3 parties mid multi-step session;
    the session HEALS — the spare party fills the dead slot, the resume
    barrier min-joins the survivors' checkpoint watermarks, only steps
    past the resume point re-run, and the merged result is byte-identical
    to an undisturbed run.  The dead party's breaker trips while the
    survivors' stay closed, and `mc_dispatch_resumes` /
    `mc_dispatch_replaced_parties` advance."""

    DEADLINE_MS = 6000
    STEPS = 80

    @pytest.fixture
    def mesh(self, tuned_flags):
        import jax

        tuned_flags("circuit_breaker_short_window_size", 30)
        tuned_flags("circuit_breaker_long_window_size", 300)
        tuned_flags("circuit_breaker_min_isolation_duration_ms", 60000)
        tuned_flags("enable_circuit_breaker", True)
        from incubator_brpc_tpu.rpc import device_method
        from incubator_brpc_tpu.rpc.device_method import (
            DeviceMethod,
            register_device_method,
        )
        from incubator_brpc_tpu.transport.mc_worker import (
            SESSION_WIDTH,
            _scale_psum_kernel,
        )

        register_device_method(
            "dsvc", "scale", DeviceMethod(_scale_psum_kernel, width=SESSION_WIDTH)
        )
        servers, channels = [], []
        for i in range(4):  # 3 parties + 1 spare
            s = Server(
                ServerOptions(
                    device_index=i + 1,
                    enable_collective_service=True,
                    collective_max_concurrency=0,
                )
            )
            s.add_service(
                "dsvc",
                {"scale": device_method(_scale_psum_kernel, width=SESSION_WIDTH)},
            )
            assert s.start(0)
            servers.append(s)
            ch = Channel()
            assert ch.init(
                f"list://127.0.0.1:{s.port}",
                lb_name="rr",
                options=ChannelOptions(max_retry=1, timeout_ms=10000),
            )
            channels.append(ch)
        party_ids = [d.id for d in jax.devices()[1:4]]
        spare_dev = jax.devices()[4].id
        yield servers, channels, party_ids, spare_dev
        from incubator_brpc_tpu.parallel import mc_dispatch

        mc_dispatch.set_step_hook(None)
        for ch in channels:
            if ch._lb is not None:
                ch._lb.stop()
        for s in servers:
            s.stop()
            s.join(timeout=5)

    def test_kill_at_step_k_heals_byte_identical(self, mesh):
        from incubator_brpc_tpu.parallel import mc_dispatch
        from incubator_brpc_tpu.transport.mc_worker import session_expected

        servers, channels, party_ids, spare_dev = mesh
        operands = [bytes([i + 1]) * 8 for i in range(3)]
        before_resumes = mc_dispatch.dispatch_resumes.get_value()
        before_replaced = mc_dispatch.dispatch_replaced_parties.get_value()

        # pace every party (30 ms a step of an 80-step run) and kill
        # party 0 from its own step hook when it enters step K: the death
        # lands mid-session whatever the host's load. A 0.35 s timer
        # decided the step it died at, and under six workers whether the
        # session had begun at all
        K = 12
        killed = threading.Event()  # the spare that fills slot 0 runs K too

        def hook(step, idx):
            if idx == 0 and step == K and not killed.is_set():
                killed.set()
                threading.Thread(
                    target=lambda: (servers[0].stop(), servers[0].join(timeout=3)),
                    daemon=True,
                ).start()
            time.sleep(0.03)

        mc_dispatch.set_step_hook(hook)
        try:
            out = mc_dispatch.propose_with_recovery(
                channels[:3],
                party_ids,
                "dsvc",
                "scale",
                operands,
                steps=self.STEPS,
                proposer_index=None,
                timeout_ms=60000,
                session_deadline_ms=self.DEADLINE_MS,
                spares=[(channels[3], spare_dev)],
                checkpoint_every=2,
            )
        finally:
            mc_dispatch.set_step_hook(None)
        assert killed.is_set()

        # healed, not shrunk: the spare filled the dead slot, the session
        # resumed from a COMMON checkpoint instead of step 0
        assert out["dead_party_ids"] == [party_ids[0]]
        assert out["replaced_party_ids"] == [spare_dev]
        assert out["resumed_from"] is not None and out["resumed_from"] > 0
        assert out["resumed_from"] % 2 == 0  # a checkpointed step
        assert out["final_steps"] == self.STEPS

        # byte-identity with an undisturbed run of the SAME party count
        want = session_expected(operands, self.STEPS)
        for i, (got, exp) in enumerate(zip(out["results"], want)):
            assert got == exp, f"slot {i} diverged after resume"

        assert mc_dispatch.dispatch_resumes.get_value() > before_resumes
        assert (
            mc_dispatch.dispatch_replaced_parties.get_value()
            > before_replaced
        )

        # blame: the dead party's breaker trips (connect-refused selects
        # feed it); the survivors' stay closed
        for _ in range(30):
            if channels[0]._lb.isolated_servers():
                break
            channels[0].call_method("dsvc", "scale", b"x")
        assert channels[0]._lb.isolated_servers(), (
            "dead party's breaker never tripped"
        )
        for i in (1, 2):
            assert not channels[i]._lb.isolated_servers(), (
                f"survivor {i}'s breaker tripped off the resumed session"
            )

    def test_two_party_session_heals_with_spare(self, mesh):
        """A 2-party session + one death CAN heal when a spare preserves
        the width — the survivor-count guard only gates the shrink path."""
        from incubator_brpc_tpu.parallel import mc_dispatch
        from incubator_brpc_tpu.transport.mc_worker import session_expected

        servers, channels, party_ids, spare_dev = mesh
        ops = [b"\x01" * 8, b"\x02" * 8]
        mc_dispatch.set_step_hook(lambda step, idx: time.sleep(0.03))
        killer = threading.Timer(
            0.3, lambda: (servers[0].stop(), servers[0].join(timeout=3))
        )
        killer.start()
        try:
            out = mc_dispatch.propose_with_recovery(
                channels[:2],
                party_ids[:2],
                "dsvc",
                "scale",
                ops,
                steps=40,
                proposer_index=None,
                timeout_ms=60000,
                session_deadline_ms=self.DEADLINE_MS,
                spares=[(channels[3], spare_dev)],
                checkpoint_every=2,
            )
        finally:
            killer.cancel()
            mc_dispatch.set_step_hook(None)
        assert out["replaced_party_ids"] == [spare_dev]
        assert out["dead_party_ids"] == [party_ids[0]]
        want = session_expected(ops, out["final_steps"])
        assert [bytes(r) for r in out["results"]] == want

    def test_quantized_overlapped_session_resumes_byte_identical(self, mesh):
        """The quantized-collective composition drill (ISSUE 14): an
        int8 chunked double-buffered pmean session killed mid-run heals
        through the SAME elastic path — and because quantized
        checkpoint rings store the block-quantized representation with
        power-of-two scales (dequantize→requantize is exactly
        idempotent), the healed chain's bytes equal an undisturbed
        run's.  No silent float32 inflation on resume: the retained
        entry is the quantized twin, at the wire's ~4x discount."""
        import numpy as np

        from incubator_brpc_tpu.parallel import mc_dispatch, quantized
        from incubator_brpc_tpu.parallel.mc_collective import _pmean_dm
        from incubator_brpc_tpu.rpc.device_method import (
            register_device_method,
        )

        from incubator_brpc_tpu.rpc.device_method import (
            lookup_device_method,
            unregister_device_method,
        )

        servers, channels, party_ids, spare_dev = mesh
        width = 256  # 64 floats = 2 blocks; chunks=2 stays block-aligned
        prev = lookup_device_method("_collective", "pmean")
        register_device_method("_collective", "pmean", _pmean_dm(width))
        rng = np.random.default_rng(21)
        rows = [
            (rng.standard_normal(width // 4) * (i + 1)).astype(np.float32)
            for i in range(3)
        ]
        operands = [r.tobytes() for r in rows]
        kw = dict(
            steps=40,
            proposer_index=None,
            timeout_ms=60000,
            session_deadline_ms=self.DEADLINE_MS,
            checkpoint_every=2,
            quantize="int8",
            chunks=2,
            double_buffer=True,
        )
        mc_dispatch.set_step_hook(lambda step, idx: time.sleep(0.03))
        try:
            # the undisturbed control: same schedule, nobody dies
            control = mc_dispatch.propose_with_recovery(
                channels[:3], party_ids, "_collective", "pmean",
                operands, **kw,
            )
            killer = threading.Timer(
                0.35, lambda: (servers[0].stop(), servers[0].join(timeout=3))
            )
            killer.start()
            try:
                out = mc_dispatch.propose_with_recovery(
                    channels[:3], party_ids, "_collective", "pmean",
                    operands, spares=[(channels[3], spare_dev)], **kw,
                )
            finally:
                killer.cancel()
        finally:
            mc_dispatch.set_step_hook(None)
            # restore exactly: a leaked registration shadows the
            # width-minting pmean resolver for later suites
            if prev is not None:
                register_device_method("_collective", "pmean", prev)
            else:
                unregister_device_method("_collective", "pmean")
        assert out["replaced_party_ids"] == [spare_dev]
        assert out["resumed_from"] is not None and out["resumed_from"] > 0
        assert out["resumed_from"] % 2 == 0
        # replay byte-identity for the quantized session killed mid-run
        for i in range(3):
            assert out["results"][i] == control["results"][i], (
                f"slot {i} diverged after quantized resume"
            )
        # the wire accounting carried the quantized footprint, counted
        # over the REPLAYED steps only (the healed run re-moved just
        # the steps past the resume point)
        assert out["quantize"] == "int8"
        replayed = out["final_steps"] - out["resumed_from"]
        assert out["wire_bytes"] == (
            quantized.wire_bytes(width, "int8") * 3 * replayed
        )
        # and the result sits inside the documented error bound of the
        # exact mean (steps compound conservatively)
        exact = np.mean(np.stack(rows), axis=0, dtype=np.float32)
        bound = quantized.pmean_error_bound(rows, out["final_steps"], "int8")
        got = np.frombuffer(out["results"][0], dtype=np.float32)
        assert float(np.abs(got - exact).max()) <= bound

    def test_no_spare_falls_back_to_shrink_restart(self, mesh):
        """Without a spare the recovery path is PR-8's: a fresh session
        from step 0 over the survivors only — never a divergent resume."""
        from incubator_brpc_tpu.parallel import mc_dispatch
        from incubator_brpc_tpu.transport.mc_worker import session_expected

        servers, channels, party_ids, _spare = mesh
        operands = [bytes([i + 1]) * 8 for i in range(3)]
        mc_dispatch.set_step_hook(lambda step, idx: time.sleep(0.03))
        killer = threading.Timer(
            0.3, lambda: (servers[0].stop(), servers[0].join(timeout=3))
        )
        killer.start()
        try:
            out = mc_dispatch.propose_with_recovery(
                channels[:3],
                party_ids,
                "dsvc",
                "scale",
                operands,
                steps=30,
                proposer_index=None,
                timeout_ms=60000,
                session_deadline_ms=self.DEADLINE_MS,
                checkpoint_every=2,
            )
        finally:
            killer.cancel()
            mc_dispatch.set_step_hook(None)
        assert out["dead_party_ids"] == [party_ids[0]]
        assert out["replaced_party_ids"] == []
        assert out["resumed_from"] is None  # restart, not resume
        # the shrunk session's result matches the SURVIVOR-set model
        want = session_expected(operands[1:], out["final_steps"])
        assert out["results"][0] == want[0] and out["results"][1] == want[1]


class TestStepWatchdog:
    """`mc_dispatch_step_deadline_ms` bounds a single lockstep step:
    a party wedged INSIDE one step aborts the session fabric-wide at
    step granularity instead of burning the whole session deadline
    (PR 8's documented gap)."""

    @pytest.fixture
    def mesh(self, tuned_flags):
        import jax

        from incubator_brpc_tpu.rpc import device_method
        from incubator_brpc_tpu.rpc.device_method import (
            DeviceMethod,
            register_device_method,
        )
        from incubator_brpc_tpu.transport.mc_worker import (
            SESSION_WIDTH,
            _scale_psum_kernel,
        )

        register_device_method(
            "dsvc", "scale", DeviceMethod(_scale_psum_kernel, width=SESSION_WIDTH)
        )
        servers, channels = [], []
        for i in range(3):
            s = Server(
                ServerOptions(
                    device_index=i + 1,
                    enable_collective_service=True,
                    collective_max_concurrency=0,
                )
            )
            s.add_service(
                "dsvc",
                {"scale": device_method(_scale_psum_kernel, width=SESSION_WIDTH)},
            )
            assert s.start(0)
            servers.append(s)
            ch = Channel()
            assert ch.init(f"127.0.0.1:{s.port}")
            channels.append(ch)
        party_ids = [d.id for d in jax.devices()[1:4]]
        yield servers, channels, party_ids
        from incubator_brpc_tpu.parallel import mc_dispatch

        mc_dispatch.set_step_hook(None)
        for s in servers:
            s.stop()
            s.join(timeout=5)

    def test_watchdog_fires_inside_stuck_step(self, mesh):
        from incubator_brpc_tpu.parallel import mc_dispatch

        servers, channels, party_ids = mesh
        operands = [bytes([i + 1]) * 8 for i in range(3)]
        before_aborts = mc_dispatch.dispatch_aborts.get_value()

        STALL_S = 2.5
        SESSION_DEADLINE_MS = 30000

        def hook(step, idx):
            if idx == 1 and step == 2:
                time.sleep(STALL_S)  # wedged inside step 2

        mc_dispatch.set_step_hook(hook)
        t0 = time.monotonic()
        with pytest.raises(mc_dispatch.SessionAborted) as exc:
            mc_dispatch.propose_dispatch(
                channels,
                party_ids,
                "dsvc",
                "scale",
                operands,
                steps=30,
                proposer_index=None,
                timeout_ms=60000,
                session_deadline_ms=SESSION_DEADLINE_MS,
                step_deadline_ms=150,
            )
        elapsed = time.monotonic() - t0
        mc_dispatch.set_step_hook(None)
        # the watchdog (not the 30 s session deadline) took it down, and
        # the blame names the step deadline
        assert elapsed < STALL_S + 4.0
        assert "step deadline" in str(exc.value)
        assert mc_dispatch.dispatch_aborts.get_value() > before_aborts
        assert wait_until(
            lambda: mc_dispatch.active_sessions() == 0, timeout=10
        )


# ---------------------------------------------------------------------------
# retry budget (SRE-style token bucket on the Channel)
# ---------------------------------------------------------------------------


class TestRetryBudget:
    def test_token_bucket_unit(self):
        from incubator_brpc_tpu.rpc.channel import (
            _RETRY_BUDGET_CAP,
            RetryBudget,
        )

        b = RetryBudget(0.5)
        for _ in range(int(_RETRY_BUDGET_CAP)):
            assert b.acquire(ErrorCode.EFAILEDSOCKET)
        assert not b.acquire(ErrorCode.EFAILEDSOCKET)  # drained
        # deposits refill at the ratio: 4 calls fund 2 retries
        for _ in range(4):
            b.on_call()
        assert b.balance() == pytest.approx(2.0)
        assert b.acquire(ErrorCode.EFAILEDSOCKET)
        assert b.acquire(ErrorCode.EFAILEDSOCKET)
        assert not b.acquire(ErrorCode.EFAILEDSOCKET)
        # the cap bounds accumulation
        for _ in range(10_000):
            b.on_call()
        assert b.balance() == pytest.approx(_RETRY_BUDGET_CAP)

    def test_exempt_codes_never_draw(self):
        from incubator_brpc_tpu.rpc.channel import (
            RETRY_BUDGET_EXEMPT,
            RetryBudget,
        )

        b = RetryBudget(0.1)
        while b.acquire(ErrorCode.EFAILEDSOCKET):
            pass  # drain it
        for code in RETRY_BUDGET_EXEMPT:
            assert b.acquire(code)  # exempt: passes without a token
        assert b.balance() < 1.0
        assert {
            ErrorCode.EDEADLINE, ErrorCode.ESESSION, ErrorCode.ELIMIT
        } == set(RETRY_BUDGET_EXEMPT)

    def test_zero_ratio_disables(self, flags):
        from incubator_brpc_tpu.rpc.channel import RetryBudget

        b = RetryBudget(0.0)
        for _ in range(200):
            assert b.acquire(ErrorCode.EFAILEDSOCKET)

    def test_exhaustion_fails_fast_with_original_error(self, flags):
        """A drained budget means the FIRST error settles the call — no
        retry storm — and the error text says why."""
        from incubator_brpc_tpu.rpc.channel import retry_budget_exhausted

        srv = Server()
        srv.add_service("e", {"m": lambda c, r: b"ok"})
        assert srv.start(0)
        port = srv.port
        srv.stop()
        srv.join(timeout=5)  # the port now refuses connections

        ch = Channel()
        assert ch.init(
            f"127.0.0.1:{port}",
            options=ChannelOptions(
                max_retry=3, timeout_ms=2000, connect_timeout=0.25
            ),
        )
        # control: with budget, a connectivity failure burns its retries
        cntl = ch.call_method("e", "m", b"x")
        assert cntl.failed()
        assert cntl.retried_count == 3, (
            f"expected retries before exhaustion, got {cntl.retried_count}"
        )
        # drain the bucket below one token: the next failure cannot retry
        before = retry_budget_exhausted.get_value()
        with ch._retry_budget._lock:
            ch._retry_budget._tokens = 0.2
        cntl = ch.call_method("e", "m", b"x")
        assert cntl.failed()
        assert cntl.retried_count == 0, "budget-exhausted call retried"
        assert "retry budget exhausted" in cntl.error_text
        assert retry_budget_exhausted.get_value() > before

    def test_budget_visible_in_vars(self, flags):
        from incubator_brpc_tpu.bvar.variable import expose_registry

        names = dict(expose_registry.snapshot())
        assert "retry_budget_tokens" in names
        assert "retry_budget_exhausted" in names
        srv = Server()
        srv.add_service("e", {"m": lambda c, r: b"ok"})
        assert srv.start(0)
        try:
            ch = Channel()
            assert ch.init(f"127.0.0.1:{srv.port}")
            assert ch.call_method("e", "m", b"x").ok()
            from incubator_brpc_tpu.rpc.channel import retry_budget_tokens

            # the live channel's full bucket shows up in the aggregate
            assert retry_budget_tokens.get_value() >= 50.0
        finally:
            srv.stop()
            srv.join(timeout=5)


# ---------------------------------------------------------------------------
# lame-duck drain covers open streaming RPCs
# ---------------------------------------------------------------------------


class TestLameDuckStreamDrain:
    @pytest.fixture
    def stream_server(self):
        from incubator_brpc_tpu.rpc import StreamHandler, StreamOptions, stream_accept

        class Recorder(StreamHandler):
            def __init__(self):
                self.closed = threading.Event()

            def on_closed(self, stream):
                self.closed.set()

        server = Server()
        accepted = {}

        def open_stream(cntl, request):
            rec = Recorder()
            s = stream_accept(cntl, StreamOptions(handler=rec))
            assert s is not None
            accepted["stream"], accepted["rec"] = s, rec
            return b"accepted"

        server.add_service("t", {"open_stream": open_stream})
        assert server.start(0)
        yield server, accepted, Recorder
        server.stop()
        server.join(timeout=5)

    def _open(self, server, Recorder):
        from incubator_brpc_tpu.rpc import StreamOptions, stream_create

        rec = Recorder()
        s = stream_create(StreamOptions(handler=rec))
        ch = Channel()
        assert ch.init(f"127.0.0.1:{server.port}")
        cntl = ch.call_method("t", "open_stream", b"", request_stream=s)
        assert cntl.ok(), cntl.error_text
        assert s.wait_connected(5)
        return s, rec

    def test_drain_waits_for_stream_close(self, stream_server):
        server, accepted, Recorder = stream_server
        s, _rec = self._open(server, Recorder)
        assert server._open_streams(), "server does not see its stream"
        t0 = time.monotonic()
        t = server.enter_lame_duck(grace_s=8.0)
        assert t is not None
        # the drain is blocked on the stream, not done instantly
        time.sleep(0.4)
        assert t.is_alive(), "drain finished under an open stream"
        s.close()
        t.join(timeout=6)
        assert not t.is_alive()
        # it proceeded on the close, long before grace expiry
        assert time.monotonic() - t0 < 6.0
        assert server._stopping

    def test_grace_expiry_rsts_open_streams(self, stream_server):
        server, accepted, Recorder = stream_server
        s, rec = self._open(server, Recorder)
        t = server.enter_lame_duck(grace_s=0.6)
        assert t is not None
        t.join(timeout=8)
        assert not t.is_alive()
        # the straggler stream died on a clean RST at grace expiry: the
        # client handler observed the close instead of a dirty socket cut
        assert rec.closed.wait(3), "client never saw the stream end"
        from incubator_brpc_tpu.rpc import stream as stream_mod

        assert s.state == stream_mod.CLOSED
        assert server._stopping
