"""Combo channel tests (reference test/brpc_parallel_channel_unittest.cpp,
brpc_selective_channel_unittest.cpp, brpc_partition_channel_unittest.cpp —
the in-process many-local-servers shape of SURVEY §4)."""

import threading
import time

import pytest

from incubator_brpc_tpu.rpc import (
    CallMapper,
    Channel,
    ParallelChannel,
    PartitionChannel,
    PartitionParser,
    ResponseMerger,
    SelectiveChannel,
    Server,
    SubCall,
)
from incubator_brpc_tpu.utils.status import ErrorCode


def make_server(name: bytes):
    """Echo server that prefixes responses with its name."""
    server = Server()

    def echo(cntl, request):
        return name + b":" + request

    def fail(cntl, request):
        cntl.set_failed(ErrorCode.EINTERNAL, "injected failure")
        return b""

    def slow(cntl, request):
        time.sleep(0.3)
        return name + b":slow"

    server.add_service("svc", {"echo": echo, "fail": fail, "slow": slow})
    assert server.start(0)
    return server


@pytest.fixture
def three_servers():
    servers = [make_server(b"s%d" % i) for i in range(3)]
    yield servers
    for s in servers:
        s.stop()
    for s in servers:
        s.join(timeout=5)


def sub_channel(server):
    ch = Channel()
    assert ch.init(f"127.0.0.1:{server.port}")
    return ch


class TestParallelChannel:
    def test_broadcast_and_merge_in_index_order(self, three_servers):
        pc = ParallelChannel()
        for s in three_servers:
            pc.add_channel(sub_channel(s))
        cntl = pc.call_method("svc", "echo", b"hi")
        assert cntl.ok(), cntl.error_text
        assert cntl.response_payload == b"s0:his1:his2:hi"

    def test_call_mapper_rewrites_and_skips(self, three_servers):
        class Mapper(CallMapper):
            def map(self, i, n, service, method, request):
                if i == 1:
                    return SubCall.skip()
                return SubCall(request=b"%d" % i)

        pc = ParallelChannel()
        for s in three_servers:
            pc.add_channel(sub_channel(s), call_mapper=Mapper())
        cntl = pc.call_method("svc", "echo", b"ignored")
        assert cntl.ok()
        assert cntl.response_payload == b"s0:0s2:2"

    def test_default_fail_limit_tolerates_partial_failure(self, three_servers):
        """Unset fail_limit = ndone: parent succeeds unless ALL fail
        (parallel_channel.cpp:625-627)."""
        class Mapper(CallMapper):
            def map(self, i, n, service, method, request):
                return SubCall(method="fail" if i == 0 else "echo")

        pc = ParallelChannel()
        for s in three_servers:
            pc.add_channel(sub_channel(s), call_mapper=Mapper())
        cntl = pc.call_method("svc", "echo", b"x")
        assert cntl.ok()
        assert cntl.response_payload == b"s1:xs2:x"  # failed sub not merged

    def test_fail_limit_one_fails_fast(self, three_servers):
        class Mapper(CallMapper):
            def map(self, i, n, service, method, request):
                return SubCall(method="fail" if i == 2 else "echo")

        pc = ParallelChannel(fail_limit=1)
        for s in three_servers:
            pc.add_channel(sub_channel(s), call_mapper=Mapper())
        cntl = pc.call_method("svc", "echo", b"x")
        assert cntl.failed()
        assert cntl.error_code == ErrorCode.EINTERNAL

    def test_all_failed_fails_parent(self, three_servers):
        pc = ParallelChannel()
        for s in three_servers:
            pc.add_channel(sub_channel(s))
        cntl = pc.call_method("svc", "fail", b"x")
        assert cntl.failed()

    def test_custom_merger(self, three_servers):
        class Longest(ResponseMerger):
            def merge(self, merged, sub):
                return sub if len(sub) > len(merged) else merged

        pc = ParallelChannel()
        names = [b"a", b"bb", b"c"]
        for s, n in zip(three_servers, names):
            pc.add_channel(sub_channel(s), response_merger=Longest())
        cntl = pc.call_method("svc", "echo", b"zz")
        assert cntl.ok()
        # all three responses are 5 bytes ("sN:zz"); merge keeps the first
        # (index order) since later ones aren't strictly longer
        assert cntl.response_payload == b"s0:zz"

    def test_async_done(self, three_servers):
        pc = ParallelChannel()
        for s in three_servers:
            pc.add_channel(sub_channel(s))
        ev = threading.Event()
        out = {}

        def done(c):
            out["payload"] = c.response_payload
            ev.set()

        pc.call_method("svc", "echo", b"a", done=done)
        assert ev.wait(timeout=5)
        assert out["payload"] == b"s0:as1:as2:a"


# -- the one merge of every lowering (rpc/combo.py merge_responses) ----------


class Recording(ResponseMerger):
    """A subclass that merges as the default does and notes every call."""

    def __init__(self, log, who):
        self.log, self.who = log, who

    def merge(self, merged, sub_response):
        self.log.append((self.who, type(merged), type(sub_response), sub_response))
        return merged + sub_response


def attribute_set(log, who):
    """A plain ``ResponseMerger`` whose instance carries a ``merge`` of its
    own: a user's merger, not the class's function."""
    merger, noting = ResponseMerger(), Recording(log, who)
    merger.merge = lambda merged, sub: noting.merge(merged, sub)
    return merger


MERGER_KINDS = {
    "default_mergers": lambda log: [ResponseMerger() for _ in range(3)],
    "one_subclass_among_defaults": lambda log: [
        ResponseMerger(), Recording(log, 1), ResponseMerger()],
    "all_subclasses": lambda log: [Recording(log, i) for i in range(3)],
    "merge_set_on_an_instance": lambda log: [
        ResponseMerger(), ResponseMerger(), attribute_set(log, 2)],
}
# which partitions' mergers note their calls, and whether the answer is joined
MERGER_NOTES = {
    "default_mergers": ([], True),
    "one_subclass_among_defaults": ([1], False),
    "all_subclasses": ([0, 1, 2], False),
    "merge_set_on_an_instance": ([2], False),
}
WIDTH = 64
ANSWER_ROWS = {
    "full_rows": [WIDTH, WIDTH, WIDTH],
    "a_short_row": [WIDTH, 17, WIDTH],
    "an_empty_last_row": [WIDTH, WIDTH, 0],
    "every_n_under_the_width": [WIDTH - 9, 1, WIDTH - 1],
}


def incremental(answers):
    """What the default mergers gave before the join: ``merged + answer``."""
    merged = b""
    for answer in answers:
        merged = merged + answer
    return merged


@pytest.mark.parametrize("handed", ["bytes", "views_of_gathered_rows"])
@pytest.mark.parametrize("rows", list(ANSWER_ROWS))
@pytest.mark.parametrize("kind", list(MERGER_KINDS))
def test_merge_responses_joins_once_or_calls_each_merger_in_order(kind, rows, handed):
    import numpy as np

    from incubator_brpc_tpu.rpc.combo import merge_responses

    gathered = np.frombuffer(
        np.random.default_rng(7).bytes(3 * WIDTH), dtype=np.uint8).reshape(3, WIDTH)
    ns = ANSWER_ROWS[rows]
    plain = [bytes(row[:n]) for row, n in zip(gathered, ns)]
    answers = plain if handed == "bytes" else [
        row[:n] for row, n in zip(gathered, ns)]
    log = []
    merged, joined = merge_responses(MERGER_KINDS[kind](log), answers)
    assert type(merged) is bytes
    assert merged == incremental(plain) == b"".join(plain)
    noting, joins = MERGER_NOTES[kind]
    assert joined is joins
    # a user's merger: once a partition, in channel order, bytes both ways
    assert [who for who, *_ in log] == noting
    assert all(a is bytes and b is bytes for _who, a, b, _sub in log)
    assert [sub for *_, sub in log] == [plain[who] for who in noting]


def test_merge_responses_keeps_what_a_merger_makes_of_the_answers():
    from incubator_brpc_tpu.rpc.combo import merge_responses

    class Framed(ResponseMerger):
        def merge(self, merged, sub_response):
            return merged + b"[" + sub_response + b"]"

    merged, joined = merge_responses(
        [ResponseMerger(), Framed(), ResponseMerger()], [b"a", b"", b"c"])
    assert (merged, joined) == (b"a[]c", False)
    assert merge_responses([], []) == (b"", True)


@pytest.mark.parametrize("kind", list(MERGER_KINDS))
def test_the_host_fan_out_merges_through_the_same_function(kind, three_servers):
    """Channel order whatever order the sub-calls end in, a failed sub-call
    left out, and a user's merger called with ``bytes`` for the others."""
    log = []
    mergers = MERGER_KINDS[kind](log)

    class FailFirst(CallMapper):
        def map(self, i, n, service, method, request):
            return SubCall(method="fail" if i == 0 else "echo")

    for mapper, want, left_out in (
            (CallMapper(), [b"s0:hi", b"s1:hi", b"s2:hi"], []),
            (FailFirst(), [None, b"s1:hi", b"s2:hi"], [0])):
        del log[:]
        pc = ParallelChannel()
        for s, merger in zip(three_servers, mergers):
            pc.add_channel(sub_channel(s), call_mapper=mapper, response_merger=merger)
        cntl = pc.call_method("svc", "echo", b"hi")
        assert cntl.ok(), cntl.error_text
        assert cntl.response_payload == b"".join(w for w in want if w is not None)
        noting = [who for who in MERGER_NOTES[kind][0] if who not in left_out]
        assert [(who, sub) for who, _a, _b, sub in log] == [
            (who, want[who]) for who in noting]


class TestSelectiveChannel:
    def test_round_robins_across_channels(self, three_servers):
        sc = SelectiveChannel()
        for s in three_servers:
            sc.add_channel(sub_channel(s))
        seen = set()
        for _ in range(6):
            cntl = sc.call_method("svc", "echo", b"q")
            assert cntl.ok()
            seen.add(cntl.response_payload)
        assert seen == {b"s0:q", b"s1:q", b"s2:q"}

    def test_failover_to_other_replica(self, three_servers):
        """A dead replica is skipped: retries go to different sub-channels
        (selective_channel.cpp retry contract)."""
        dead = Channel()
        # unused port: connect will fail → retriable EFAILEDSOCKET
        assert dead.init("127.0.0.1:1")
        sc = SelectiveChannel(max_retry=2)
        sc.add_channel(dead)
        sc.add_channel(sub_channel(three_servers[0]))
        for _ in range(4):
            cntl = sc.call_method("svc", "echo", b"f")
            assert cntl.ok(), cntl.error_text
            assert cntl.response_payload == b"s0:f"

    def test_application_error_does_not_failover(self, three_servers):
        sc = SelectiveChannel(max_retry=2)
        for s in three_servers:
            sc.add_channel(sub_channel(s))
        cntl = sc.call_method("svc", "fail", b"x")
        assert cntl.failed()
        assert cntl.error_code == ErrorCode.EINTERNAL

    def test_async_done_does_not_block(self, three_servers):
        sc = SelectiveChannel()
        for s in three_servers:
            sc.add_channel(sub_channel(s))
        ev = threading.Event()
        out = {}

        def done(c):
            out["p"] = c.response_payload
            ev.set()

        t0 = time.monotonic()
        sc.call_method("svc", "slow", b"x", done=done)
        assert time.monotonic() - t0 < 0.2  # returned before the 0.3s handler
        assert ev.wait(timeout=5)
        assert out["p"].endswith(b":slow")

    def test_per_call_deadline_covers_all_retries(self):
        """The caller's timeout bounds the WHOLE call, not each attempt
        (controller deadline semantics)."""
        from incubator_brpc_tpu.rpc import Controller

        sc = SelectiveChannel(max_retry=5)
        for port in (1, 2, 3):
            ch = Channel()
            assert ch.init(f"127.0.0.1:{port}")
            sc.add_channel(ch)
        cntl = Controller(timeout_ms=400, max_retry=5)
        t0 = time.monotonic()
        sc.call_method("svc", "echo", b"x", cntl=cntl)
        assert cntl.failed()
        assert time.monotonic() - t0 < 2.0  # not 6 x timeout

    def test_all_dead_fails(self):
        sc = SelectiveChannel(max_retry=3)
        for port in (1, 2):
            ch = Channel()
            assert ch.init(f"127.0.0.1:{port}")
            sc.add_channel(ch)
        cntl = sc.call_method("svc", "echo", b"x")
        assert cntl.failed()


class _ScriptedSub:
    """Stand-in sub-channel whose outcomes are driven by the test: lets the
    health state machine be exercised deterministically (the reference
    tests its SelectiveChannel health path with controllable fake
    SocketIds the same way)."""

    def __init__(self):
        self.healthy = True
        self.calls = 0

    def call_method(self, service, method, request, cntl=None, done=None,
                    attachment=b""):
        self.calls += 1
        if self.healthy:
            cntl.response_payload = b"ok:" + request
        else:
            cntl.set_failed(ErrorCode.EFAILEDSOCKET, "scripted transport down")
        if done:
            done(cntl)
        return cntl


class TestSelectiveChannelHealth:
    """The embedded LB integrates health: a sub-channel with consecutive
    transport failures leaves the candidate set until its backed-off
    revive probe (the reference excludes a failed fake Socket until the
    health check revives it, selective_channel.cpp + socket health loop)."""

    def test_downed_sub_is_excluded_until_revive_probe(self):
        a, b = _ScriptedSub(), _ScriptedSub()
        b.healthy = False
        sc = SelectiveChannel(
            max_retry=2, lb_name="rr",
            health_check_fails=2, health_check_interval_s=0.3,
        )
        sc.add_channel(a)
        sc.add_channel(b)
        # drive calls: b fails its first attempts, hits the streak
        # threshold, and is downed; every call still succeeds via a
        for _ in range(10):
            cntl = sc.call_method("s", "m", b"x")
            assert cntl.ok(), cntl.error_text
        health = {h["index"]: h for h in sc.health()}
        assert health[1]["down"], health
        b_calls_when_downed = b.calls
        # b is OUT of the candidate set: further traffic never touches it
        for _ in range(10):
            assert sc.call_method("s", "m", b"x").ok()
        assert b.calls == b_calls_when_downed, "downed sub still picked"
        # after the interval, the next call probes b in place; still dead
        # -> downed again with doubled backoff, traffic stays on a
        time.sleep(0.35)
        for _ in range(6):
            assert sc.call_method("s", "m", b"x").ok()
        assert b.calls == b_calls_when_downed + 1, "revive probe count"
        # now b recovers; at the next revive probe it serves again and is
        # restored as a full candidate (streak reset, backoff reset)
        b.healthy = True
        time.sleep(0.65)  # doubled backoff
        for _ in range(8):
            assert sc.call_method("s", "m", b"x").ok()
        health = {h["index"]: h for h in sc.health()}
        assert not health[1]["down"], health
        assert b.calls > b_calls_when_downed + 1, "recovered sub not reused"

    def test_all_down_still_probes_rather_than_failing(self):
        a = _ScriptedSub()
        a.healthy = False
        sc = SelectiveChannel(
            max_retry=1, lb_name="rr",
            health_check_fails=1, health_check_interval_s=5.0,
        )
        sc.add_channel(a)
        # first call downs it; second call has NO healthy candidate — the
        # degraded path probes the downed sub instead of failing without
        # an attempt
        assert sc.call_method("s", "m", b"x").failed()
        calls_before = a.calls
        cntl = sc.call_method("s", "m", b"x")
        assert cntl.failed()
        assert a.calls > calls_before, "no probe attempted when all down"

    def test_real_server_outage_shifts_traffic_off_the_replica(self):
        """Integration shape: one replica's server dies mid-traffic; the
        health gate takes it out of rotation (not merely per-call retry),
        and throughput continues on the survivor."""
        alive = make_server(b"alive")
        dying = make_server(b"dying")
        sc = SelectiveChannel(
            max_retry=2, lb_name="rr",
            health_check_fails=2, health_check_interval_s=30.0,
        )
        for srv in (alive, dying):
            sc.add_channel(sub_channel(srv))
        try:
            for _ in range(4):
                assert sc.call_method("svc", "echo", b"w").ok()
            dying.stop()
            dying.join(timeout=5)
            # the first couple of calls may pay the failed attempt; once
            # the streak downs the replica, calls go straight to alive
            for _ in range(8):
                cntl = sc.call_method("svc", "echo", b"w")
                assert cntl.ok(), cntl.error_text
                assert cntl.response_payload == b"alive:w"
            health = {h["index"]: h for h in sc.health()}
            assert health[1]["down"], health
        finally:
            alive.stop()
            alive.join(timeout=5)


class TestNamingTagDiff:
    def test_tag_change_is_remove_then_add(self, tmp_path):
        """A tag-only change must reach observers as remove-then-add so
        tag-blind LBs keep the server (reference ServerNode tag compare)."""
        from incubator_brpc_tpu.naming import NamingServiceThread

        f = tmp_path / "servers"
        f.write_text("127.0.0.1:7001 0/2\n")
        nst = NamingServiceThread(f"file://{f}")
        nst.stop()  # no timer; we drive _refresh by hand
        events = []

        class Obs:
            def add_server(self, ep):
                events.append(("add", ep.port, ep.tag))

            def remove_server(self, ep):
                events.append(("rm", ep.port, ep.tag))

        nst._refresh()
        nst.add_observer(Obs())
        f.write_text("127.0.0.1:7001 1/2\n")
        nst._refresh()
        assert events == [
            ("add", 7001, "0/2"),  # add_observer replay
            ("rm", 7001, "0/2"),
            ("add", 7001, "1/2"),
        ]

    def test_one_address_two_tags_both_tracked(self, tmp_path):
        from incubator_brpc_tpu.naming import NamingServiceThread

        f = tmp_path / "servers"
        f.write_text("127.0.0.1:7002 0/2\n127.0.0.1:7002 1/2\n")
        nst = NamingServiceThread(f"file://{f}")
        nst.stop()
        nst._refresh()
        assert {(ep.port, ep.tag) for ep in nst.servers()} == {
            (7002, "0/2"),
            (7002, "1/2"),
        }
        removed = []

        class Obs:
            def add_server(self, ep):
                pass

            def remove_server(self, ep):
                removed.append(ep.tag)

        nst.add_observer(Obs())
        f.write_text("\n")
        nst._refresh()
        assert sorted(removed) == ["0/2", "1/2"]


class TestPartitionChannel:
    def test_parser(self):
        p = PartitionParser()
        assert p.parse("0/3") == (0, 3)
        assert p.parse("2/3") == (2, 3)
        assert p.parse("3/3") is None
        assert p.parse("junk") is None
        assert p.parse("") is None

    def test_fanout_across_partitions(self, three_servers):
        """Each partition's sub-channel only sees its tagged servers; the
        call fans out across partitions and merges."""
        url = "list://" + ",".join(
            f"127.0.0.1:{s.port} {i}/3" for i, s in enumerate(three_servers)
        )
        pc = PartitionChannel()
        assert pc.init(url, partition_count=3)
        cntl = pc.call_method("svc", "echo", b"p")
        assert cntl.ok(), cntl.error_text
        assert cntl.response_payload == b"s0:ps1:ps2:p"
        pc.stop()

    def test_untagged_servers_excluded(self, three_servers):
        # only partitions 0 and 1 are tagged; server 2 has a foreign tag
        url = "list://" + ",".join(
            [
                f"127.0.0.1:{three_servers[0].port} 0/2",
                f"127.0.0.1:{three_servers[1].port} 1/2",
                f"127.0.0.1:{three_servers[2].port} other",
            ]
        )
        pc = PartitionChannel()
        assert pc.init(url, partition_count=2)
        cntl = pc.call_method("svc", "echo", b"u")
        assert cntl.ok()
        assert cntl.response_payload == b"s0:us1:u"
        pc.stop()

    def test_empty_partition_fails_sub_call(self, three_servers):
        """A partition with no servers fails its sub-call; default
        fail_limit still lets the others succeed."""
        url = "list://" + ",".join(
            [
                f"127.0.0.1:{three_servers[0].port} 0/2",
                # partition 1 is empty
            ]
        )
        pc = PartitionChannel()
        assert pc.init(url, partition_count=2)
        cntl = pc.call_method("svc", "echo", b"e")
        assert cntl.ok(), cntl.error_text
        assert cntl.response_payload == b"s0:e"
        pc.stop()


class TestSelectiveChannelEmbeddedLB:
    def test_la_lb_prefers_the_fast_replica(self):
        # two replicas, one slow: the embedded locality-aware LB should
        # shift traffic to the fast one (the reference's embedded-LB
        # contract over fake SocketIds, selective_channel.cpp)
        import time as _time

        fast = Server()
        fast.add_service("s", {"m": lambda cntl, req: b"fast"})
        assert fast.start(0)
        slow = Server()

        def slow_m(cntl, req):
            _time.sleep(0.05)
            return b"slow"

        slow.add_service("s", {"m": slow_m})
        assert slow.start(0)
        try:
            sc = SelectiveChannel(lb_name="la")
            for srv in (fast, slow):
                ch = Channel()
                assert ch.init(f"127.0.0.1:{srv.port}")
                sc.add_channel(ch)
            results = []
            for _ in range(30):
                c = sc.call_method("s", "m", b"")
                assert c.ok(), c.error_text
                results.append(c.response_payload)
            # after warmup the LA scheduler should strongly prefer fast
            tail = results[10:]
            assert tail.count(b"fast") > tail.count(b"slow"), tail
        finally:
            fast.stop()
            fast.join(timeout=5)
            slow.stop()
            slow.join(timeout=5)

    def test_failed_replica_excluded_then_recovers_selection(self):
        alive = Server()
        alive.add_service("s", {"m": lambda cntl, req: b"ok"})
        assert alive.start(0)
        dead = Server()
        dead.add_service("s", {"m": lambda cntl, req: b"dead"})
        assert dead.start(0)
        dead_port = dead.port
        dead.stop()
        dead.join(timeout=5)
        try:
            sc = SelectiveChannel(max_retry=2, lb_name="rr")
            for target in (f"127.0.0.1:{dead_port}", f"127.0.0.1:{alive.port}"):
                ch = Channel()
                assert ch.init(target)
                sc.add_channel(ch)
            for _ in range(4):
                c = sc.call_method("s", "m", b"")
                assert c.ok(), c.error_text
                assert c.response_payload == b"ok"
        finally:
            alive.stop()
            alive.join(timeout=5)
