"""fabriclint — FFI-boundary & hot-path static analysis (tools/fabriclint).

Two kinds of tests:

1. **The repo is clean**: every checker runs over the live tree inside
   tier-1 and must report zero unannotated violations.  These tests ARE
   the lint gate — a PR that drifts a ctypes signature, adds a dead
   flag, or puts a per-record loop on a hotpath function fails here.
2. **The checkers work**: seeded mutations (a width change in one
   tbnet.h signature, a dropped argument, a struct field resize...)
   must flip the FFI checker red; synthetic sources prove each hotpath/
   keepalive/errcheck rule fires and each annotation form is enforced.

The sanitizer harness (`make san`) is exercised by slow, probe-gated
tests at the bottom: where the toolchain supports ASAN/TSAN they run
the real thing; elsewhere they skip cleanly.
"""

from __future__ import annotations

import os

import pytest

from tools.fabriclint import (
    RULES,
    cdecl,
    errcheck,
    ffi_check,
    hotpath,
    lifetime,
    registry_lint,
    run_all,
    scan_annotations,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fmt(violations):
    return "\n".join(str(v) for v in violations)


# ---------------------------------------------------------------------------
# 1. the live tree is clean (the lint gate)
# ---------------------------------------------------------------------------


class TestRepoIsClean:
    def test_ffi_signatures_match_headers(self):
        vs = ffi_check.check()
        assert not vs, _fmt(vs)

    def test_hotpath_functions_are_pure(self):
        vs = hotpath.check()
        assert not vs, _fmt(vs)

    def test_flag_and_bvar_registries(self):
        vs = registry_lint.check()
        assert not vs, _fmt(vs)

    def test_ffi_callbacks_have_keepalives(self):
        vs = lifetime.check()
        assert not vs, _fmt(vs)

    def test_tb_error_codes_checked_or_voided(self):
        vs = errcheck.check()
        assert not vs, _fmt(vs)

    def test_run_all_aggregate(self):
        vs = run_all()
        assert not vs, _fmt(vs)


# ---------------------------------------------------------------------------
# 2a. the header parser models the real headers completely
# ---------------------------------------------------------------------------


class TestHeaderParser:
    @pytest.fixture(scope="class")
    def merged(self):
        return ffi_check.parse_repo_headers()

    def test_every_declaration_parsed(self, merged):
        assert merged.unparsed == []

    def test_function_count_matches_sigs(self, merged):
        from incubator_brpc_tpu import native

        assert set(merged.funcs) == set(native.SIGNATURES)

    def test_telemetry_record_is_64_bytes(self, merged):
        # grown 48 -> 64 in ISSUE 15 (wire trace_id + span_id ride it)
        assert merged.structs["tb_telemetry_record"].size_bits == 64 * 8

    def test_callback_typedefs_present(self, merged):
        assert {
            "tb_frame_fn",
            "tb_handoff_fn",
            "tb_closed_fn",
            "tb_native_fn",
            "tb_release_fn",
        } <= set(merged.funcptrs)

    def test_one_line_extern_c_declaration_still_parses(self):
        # the one-line form must not vanish: it either parses (and then
        # trips ffi-unbound) or lands in unparsed — never silently gone
        src = (
            'extern "C" int tb_one_liner(int x);\n'
            'extern "C" {\n'
            "int tb_block_form(int y);\n"
            "}\n"
        )
        h = cdecl.parse_header("/synthetic.h", text=src)
        assert set(h.funcs) == {"tb_one_liner", "tb_block_form"}
        assert h.unparsed == []

    def test_scalar_canonicalization(self, merged):
        h = merged
        t = cdecl.parse_type("const char*", h)
        assert t.kind == "ptr" and t.pointee == "char"
        t = cdecl.parse_type("uint64_t", h)
        assert (t.bits, t.signed_) == (64, False)
        t = cdecl.parse_type("long", h)
        assert (t.bits, t.signed_) == (64, True)
        assert cdecl.parse_type("tb_iobuf*", h).pointee == "opaque:tb_iobuf"


# ---------------------------------------------------------------------------
# 2b. seeded mutations flip the FFI checker red (the meta-tests)
# ---------------------------------------------------------------------------


class TestFfiCheckerCatchesDrift:
    @pytest.fixture(scope="class")
    def tbnet_text(self):
        with open(os.path.join(REPO, "src", "tbnet", "tbnet.h")) as fh:
            return fh.read()

    def _mutate(self, text, old, new):
        assert old in text, f"mutation anchor missing: {old!r}"
        return text.replace(old, new)

    def test_width_change_in_one_signature(self, tbnet_text):
        # the acceptance-criterion mutation: int -> long on
        # tb_server_listen's port parameter (32 -> 64 bits)
        mut = self._mutate(tbnet_text, "const char* ip, int port)",
                           "const char* ip, long port)")
        vs = ffi_check.check(tbnet_text=mut)
        assert any(
            v.rule == "ffi-type" and "tb_server_listen" in v.message
            for v in vs
        ), _fmt(vs)

    def test_width_change_in_new_reactor_export(self, tbnet_text):
        # ISSUE 9 acceptance: a seeded width flip in one of the NEW
        # multi-reactor exports still flips the checker red — the FFI
        # gate covers the grown surface, not just the seed's
        mut = self._mutate(
            tbnet_text,
            "int tb_server_reactor_stats(const tb_server* s, int reactor,",
            "int tb_server_reactor_stats(const tb_server* s, long reactor,",
        )
        vs = ffi_check.check(tbnet_text=mut)
        assert any(
            v.rule == "ffi-type" and "tb_server_reactor_stats" in v.message
            for v in vs
        ), _fmt(vs)

    def test_width_change_in_auth_export(self, tbnet_text):
        # ISSUE 11 acceptance: the FFI gate covers the new compress/auth
        # surface too — a width flip in tb_server_set_auth_tokens' blob
        # length flips the checker red
        mut = self._mutate(
            tbnet_text,
            "int tb_server_set_auth_tokens(tb_server* s, const char* blob,\n"
            "                              size_t blob_len);",
            "int tb_server_set_auth_tokens(tb_server* s, const char* blob,\n"
            "                              int blob_len);",
        )
        vs = ffi_check.check(tbnet_text=mut)
        assert any(
            v.rule == "ffi-type" and "tb_server_set_auth_tokens" in v.message
            for v in vs
        ), _fmt(vs)

    def test_width_change_in_the_operand_writer(self):
        # PR 53: the gate covers tb_stack_rows, the one tbutil export that
        # takes an array of pointers: a row width narrowed to int, or the
        # sources passed as one pointer, flips the checker red
        with open(os.path.join(REPO, "src", "tbutil", "tbutil.h")) as fh:
            tbutil_text = fh.read()
        args = ffi_check.parse_repo_headers().funcs["tb_stack_rows"].args
        assert [(a.kind, a.pointee) for a in args if a.kind == "ptr"] == [
            ("ptr", "void"), ("ptr", "ptr"), ("ptr", "scalar:size_t")]
        assert len(args) == 6
        for old, new in (
            ("size_t row_bytes, const void** srcs", "int row_bytes, const void** srcs"),
            ("const void** srcs", "const void* srcs"),
            ("const size_t* lens", "const uint32_t* lens"),
        ):
            vs = ffi_check.check(tbutil_text=self._mutate(tbutil_text, old, new))
            assert any(
                v.rule == "ffi-type" and "tb_stack_rows" in v.message for v in vs
            ), (old, _fmt(vs))

    def test_skewed_telemetry_record_layout(self, tbnet_text):
        # ISSUE 15 acceptance: the record grew 48 -> 64 bytes (trace_id
        # + span_id); a skewed field width in the header flips the
        # 3-way struct check red — the ctypes mirror AND the numpy
        # drain dtype both disagree with the mutated C layout
        mut = self._mutate(
            tbnet_text,
            "  uint64_t trace_id;\n  uint64_t span_id;\n} tb_telemetry_record;",
            "  uint32_t trace_id;\n  uint32_t span_id;\n} tb_telemetry_record;",
        )
        vs = ffi_check.check(tbnet_text=mut)
        assert any(
            v.rule == "ffi-struct" and "tb_telemetry_record" in v.message
            for v in vs
        ), _fmt(vs)

    def test_skewed_scan_trace_out_param_flips_red(self, tbnet_text):
        # the grown tb_scan_prpc_meta trace out-params are covered by
        # the signature gate too: narrowing trace_id_out flips red
        mut = self._mutate(
            tbnet_text,
            "uint64_t* log_id_out, uint64_t* trace_id_out,",
            "uint64_t* log_id_out, uint32_t* trace_id_out,",
        )
        vs = ffi_check.check(tbnet_text=mut)
        assert any(
            v.rule == "ffi-type" and "tb_scan_prpc_meta" in v.message
            for v in vs
        ), _fmt(vs)

    def test_auth_callback_layout_drift_flips_red(self, tbnet_text):
        # the tb_auth_fn <-> AUTH_FN layout is checked field-for-field:
        # dropping the peer-port argument is an ffi-callback violation
        mut = self._mutate(
            tbnet_text,
            "typedef int (*tb_auth_fn)(void* ud, const char* auth_data, "
            "size_t auth_len,\n"
            "                          const char* peer_ip, int peer_port);",
            "typedef int (*tb_auth_fn)(void* ud, const char* auth_data, "
            "size_t auth_len,\n"
            "                          const char* peer_ip);",
        )
        vs = ffi_check.check(tbnet_text=mut)
        assert any(v.rule == "ffi-callback" for v in vs), _fmt(vs)

    def test_signedness_change(self, tbnet_text):
        mut = self._mutate(
            tbnet_text,
            "uint64_t tb_server_telemetry_dropped",
            "int64_t tb_server_telemetry_dropped",
        )
        vs = ffi_check.check(tbnet_text=mut)
        assert any(
            v.rule == "ffi-type"
            and "tb_server_telemetry_dropped" in v.message
            and "signedness" in v.message
            for v in vs
        ), _fmt(vs)

    def test_dropped_argument(self, tbnet_text):
        mut = self._mutate(
            tbnet_text,
            "void tb_server_set_telemetry(tb_server* s, uint32_t capacity,\n"
            "                             uint32_t sample_every);",
            "void tb_server_set_telemetry(tb_server* s, uint32_t capacity);",
        )
        vs = ffi_check.check(tbnet_text=mut)
        assert any(v.rule == "ffi-arity" for v in vs), _fmt(vs)

    def test_callback_layout_change(self, tbnet_text):
        mut = self._mutate(tbnet_text, "uint32_t cid_lo,\n                            uint32_t cid_hi, uint32_t flags",
                           "uint64_t cid_lo,\n                            uint32_t cid_hi, uint32_t flags")
        vs = ffi_check.check(tbnet_text=mut)
        assert any(v.rule == "ffi-callback" for v in vs), _fmt(vs)

    def test_struct_field_resize(self, tbnet_text):
        mut = self._mutate(
            tbnet_text, "uint32_t request_size;", "uint64_t request_size;"
        )
        vs = ffi_check.check(tbnet_text=mut)
        struct_vs = [v for v in vs if v.rule == "ffi-struct"]
        # the 48-byte ABI is mirrored twice: ctypes Structure AND the
        # numpy drain dtype — both must scream
        assert any("TelemetryRecord" in v.message or "ctypes" in v.message
                   or "offset" in v.message for v in struct_vs), _fmt(vs)
        assert any("numpy" in v.message for v in struct_vs), _fmt(vs)

    def test_removed_declaration_is_ffi_missing(self, tbnet_text):
        mut = self._mutate(
            tbnet_text, "int tb_server_port(const tb_server* s);", ""
        )
        vs = ffi_check.check(tbnet_text=mut)
        assert any(
            v.rule == "ffi-missing" and "tb_server_port" in v.message
            for v in vs
        ), _fmt(vs)

    def test_new_unbound_export_is_flagged(self, tbnet_text):
        mut = self._mutate(
            tbnet_text,
            "int tb_server_port(const tb_server* s);",
            "int tb_server_port(const tb_server* s);\n"
            "int tb_server_shiny_new_api(tb_server* s);",
        )
        vs = ffi_check.check(tbnet_text=mut)
        assert any(
            v.rule == "ffi-unbound" and "tb_server_shiny_new_api" in v.message
            for v in vs
        ), _fmt(vs)


# ---------------------------------------------------------------------------
# 2c. annotation grammar is enforced
# ---------------------------------------------------------------------------


class TestAnnotations:
    def test_allow_reason_must_be_nonempty(self, tmp_path):
        p = tmp_path / "m.py"
        p.write_text("# fabriclint: allow(hotpath-loop)\nx = 1\n")
        ann = scan_annotations(str(p))
        assert len(ann.bad) == 1 and ann.bad[0].rule == "bad-allow"
        assert "no reason" in ann.bad[0].message

    def test_allow_unknown_rule_is_flagged(self, tmp_path):
        p = tmp_path / "m.py"
        p.write_text("# fabriclint: allow(no-such-rule) because\n")
        ann = scan_annotations(str(p))
        assert len(ann.bad) == 1 and "unknown rule" in ann.bad[0].message

    def test_allow_inside_string_literal_is_ignored(self, tmp_path):
        p = tmp_path / "m.py"
        p.write_text('s = "# fabriclint: allow(hotpath-loop)"\n')
        ann = scan_annotations(str(p))
        assert not ann.bad and not ann.allows

    def test_rules_list_is_closed(self):
        assert "hotpath-lock" in RULES and "ffi-unchecked" in RULES


# ---------------------------------------------------------------------------
# 2d. hotpath purity rules fire (synthetic sources)
# ---------------------------------------------------------------------------

_HOTPATH_BAD = '''
import threading, logging, time
logger = logging.getLogger(__name__)

# fabriclint: hotpath
def drain(self, records):
    with self._lock:
        pass
    self._lock.acquire()
    logger.info("tick")
    print("tick")
    time.sleep(0.1)
    for r in records:
        pass
    squares = [r * r for r in records]
    while records:
        records.pop()
'''

_HOTPATH_OK = '''
import logging
logger = logging.getLogger(__name__)

# fabriclint: hotpath
def drain(self, arr):
    total = arr.sum()
    # fabriclint: allow(hotpath-loop) bounded by distinct methods, not records
    for m in set(arr.tolist()):
        total += m
    try:
        total /= len(arr)
    except ZeroDivisionError:
        logger.exception("error paths may log")
    return total

def unmarked(records):
    for r in records:  # no marker: not on the hot path
        pass
'''


class TestHotpathRules:
    def test_all_rules_fire(self):
        vs = hotpath.check_source("/synthetic/bad.py", _HOTPATH_BAD)
        rules = sorted({v.rule for v in vs})
        assert rules == [
            "hotpath-io", "hotpath-lock", "hotpath-log", "hotpath-loop",
        ], _fmt(vs)
        loops = [v for v in vs if v.rule == "hotpath-loop"]
        assert len(loops) == 3  # for + comprehension + while

    def test_allows_and_handlers_and_unmarked(self):
        vs = hotpath.check_source("/synthetic/ok.py", _HOTPATH_OK)
        assert not vs, _fmt(vs)

    def test_detached_marker_is_flagged(self):
        src = "# fabriclint: hotpath\n\n\nx = 1\n"
        vs = hotpath.check_source("/synthetic/detached.py", src)
        assert len(vs) == 1 and "not attached" in vs[0].message


# ---------------------------------------------------------------------------
# 2e. keepalive + errcheck rules fire (synthetic sources)
# ---------------------------------------------------------------------------

_KEEPALIVE_BAD = '''
from incubator_brpc_tpu.native import FRAME_FN, LIB

def start(srv, handler):
    LIB.tb_server_set_frame_cb(srv, FRAME_FN(handler), None)
'''

_KEEPALIVE_LOCAL = '''
from incubator_brpc_tpu.native import FRAME_FN, LIB

def start(srv, handler):
    cb = FRAME_FN(handler)  # dies with this frame
    LIB.tb_server_set_frame_cb(srv, cb, None)
'''

_KEEPALIVE_OK = '''
from incubator_brpc_tpu.native import FRAME_FN, LIB

class Plane:
    def __init__(self, srv, handler):
        self._cb = FRAME_FN(handler)
        LIB.tb_server_set_frame_cb(srv, self._cb, None)
'''

_ERRCHECK_SRC = '''
from incubator_brpc_tpu.native import LIB

def f(token, srv):
    LIB.tb_conn_close(token)                      # discarded: violation
    rc = LIB.tb_server_listen(srv, b"0.0.0.0", 0)  # checked: fine
    LIB.tb_server_stop(srv)                        # void restype: fine
    # fabriclint: allow(ffi-unchecked) teardown path, stale token expected
    LIB.tb_conn_close(token)
    return rc
'''


class TestLifetimeAndErrcheck:
    def test_inline_callback_is_flagged(self):
        vs = lifetime.check_source("/synthetic/ka.py", _KEEPALIVE_BAD)
        assert len(vs) == 1 and vs[0].rule == "ffi-keepalive", _fmt(vs)

    def test_frame_local_callback_is_flagged(self):
        vs = lifetime.check_source("/synthetic/ka.py", _KEEPALIVE_LOCAL)
        assert len(vs) == 1 and vs[0].rule == "ffi-keepalive", _fmt(vs)

    def test_self_attribute_keepalive_passes(self):
        vs = lifetime.check_source("/synthetic/ka.py", _KEEPALIVE_OK)
        assert not vs, _fmt(vs)

    def test_frame_local_holder_attribute_is_flagged(self):
        # holder dies with the frame even though the access spells like
        # an attribute — only module-level receivers are retained
        src = (
            "from incubator_brpc_tpu.native import FRAME_FN, LIB\n"
            "def start(srv, make_holder, h):\n"
            "    holder = make_holder(h)\n"
            "    LIB.tb_server_set_frame_cb(srv, holder.cb, None)\n"
        )
        vs = lifetime.check_source("/synthetic/ka.py", src)
        assert len(vs) == 1 and vs[0].rule == "ffi-keepalive", _fmt(vs)

    def test_discarded_return_flagged_checked_and_voided_pass(self):
        vs = errcheck.check_source("/synthetic/ec.py", _ERRCHECK_SRC)
        assert len(vs) == 1 and vs[0].rule == "ffi-unchecked", _fmt(vs)
        assert vs[0].line == 5


# ---------------------------------------------------------------------------
# 2f. registry rules fire (synthetic package trees)
# ---------------------------------------------------------------------------


class TestRegistryRules:
    def _pkg_file(self, tmp_path, name, source):
        d = tmp_path / "incubator_brpc_tpu"
        d.mkdir(exist_ok=True)
        p = d / name
        p.write_text(source)
        return str(p)

    def test_dead_flag_flagged_read_flag_passes(self, tmp_path):
        p = self._pkg_file(
            tmp_path, "flags_mod.py",
            'from incubator_brpc_tpu.utils.flags import define_flag, get_flag\n'
            'define_flag("zombie_knob", 1, "never read")\n'
            'define_flag("live_knob", 2, "read below")\n'
            'def f():\n    return get_flag("live_knob")\n',
        )
        vs = registry_lint.check_flags([p])
        assert len(vs) == 1 and vs[0].rule == "flag-dead", _fmt(vs)
        assert "zombie_knob" in vs[0].message

    def test_dict_get_does_not_mask_dead_flag(self, tmp_path):
        # a plain dict .get("name") sharing the flag's name is NOT a
        # flag read — only get_flag aliases / flag_registry.get count
        p = self._pkg_file(
            tmp_path, "flags_mod.py",
            'from incubator_brpc_tpu.utils.flags import define_flag\n'
            'from incubator_brpc_tpu.utils.flags import flag_registry\n'
            'define_flag("shadow_knob", 1, "read only as a dict key")\n'
            'define_flag("registry_knob", 2, "read via the registry")\n'
            'def f(ctx):\n'
            '    _ = ctx.get("shadow_knob")\n'
            '    return flag_registry.get("registry_knob")\n',
        )
        vs = registry_lint.check_flags([p])
        assert len(vs) == 1 and vs[0].rule == "flag-dead", _fmt(vs)
        assert "shadow_knob" in vs[0].message

    def test_flag_without_help_flagged(self, tmp_path):
        p = self._pkg_file(
            tmp_path, "flags_mod.py",
            'from incubator_brpc_tpu.utils.flags import define_flag, get_flag\n'
            'define_flag("mute_knob", 1)\n'
            'def f():\n    return get_flag("mute_knob")\n',
        )
        vs = registry_lint.check_flags([p])
        assert len(vs) == 1 and vs[0].rule == "flag-undocumented", _fmt(vs)

    def test_invalid_bvar_name_flagged(self, tmp_path):
        p = self._pkg_file(
            tmp_path, "bvars_mod.py",
            'from incubator_brpc_tpu.bvar import Adder\n'
            'bad = Adder(name="native plane calls")\n',
        )
        vs = registry_lint.check_bvars([p])
        assert any(v.rule == "bvar-name" for v in vs), _fmt(vs)

    def test_undocumented_native_bvar_flagged(self, tmp_path):
        p = self._pkg_file(
            tmp_path, "bvars_mod.py",
            'from incubator_brpc_tpu.bvar import Adder\n'
            'x = Adder(name="native_totally_new_counter")\n'
            'y = Adder(name="unprefixed_counter_is_fine")\n',
        )
        vs = registry_lint.check_bvars([p])
        assert len(vs) == 1 and vs[0].rule == "bvar-undocumented", _fmt(vs)
        assert "native_totally_new_counter" in vs[0].message

    def test_documented_native_bvar_passes(self, tmp_path):
        p = self._pkg_file(
            tmp_path, "bvars_mod.py",
            'from incubator_brpc_tpu.bvar import Adder\n'
            'x = Adder(name="native_client_calls")\n',
        )
        vs = registry_lint.check_bvars([p])
        assert not vs, _fmt(vs)

    def test_device_families_are_held_to_the_whole_name(self, tmp_path):
        """device_transport_* and device_link_* names are read by the
        benchmark by name: a per-link name built from a local prefix is
        resolved and must be in the document as device_link_<n>_<suffix>."""
        p = self._pkg_file(
            tmp_path, "bvars_mod.py",
            'from incubator_brpc_tpu.bvar import Adder, LatencyRecorder\n'
            'a = Adder(name="device_transport_not_a_documented_counter")\n'
            'b = LatencyRecorder(name="device_transport_launch_us")\n'
            'def make(link_id):\n'
            '    pfx = f"device_link_{link_id}"\n'
            '    c = LatencyRecorder(name=f"{pfx}_launch_us")\n'
            '    d = LatencyRecorder(name=f"{pfx}_never_written_down_us")\n'
            '    return c, d\n',
        )
        vs = registry_lint.check_bvars([p])
        assert [v.rule for v in vs] == ["bvar-undocumented"] * 2, _fmt(vs)
        assert "device_transport_not_a_documented_counter" in vs[0].message
        assert "device_link_<n>_never_written_down_us" in vs[1].message

    @pytest.mark.parametrize("name", [
        "device_transport_copy_us", "device_transport_credit_wait_us",
        "device_transport_queue_wait_us", "device_transport_stack_us",
        "device_transport_launch_us", "device_transport_cq_wait_us",
        "device_transport_ready_us", "device_transport_readback_us",
        "device_transport_wake_us", "device_transport_ingress_us",
        "device_transport_plane_callback_us", "device_transport_egress_us",
        "device_transport_latency", "device_transport_dispatches",
        "device_transport_dispatch_rows", "device_transport_dispatch_pad_rows",
        "device_transport_dispatch_words", "device_link_capacity_bytes",
        "device_link_<n>_step_rtt_us", "device_link_<n>_flush_us",
        "device_link_<n>_launch_us", "device_link_<n>_ready_us",
        "device_link_<n>_reorder_wait_us", "device_link_<n>_readback_us",
        "device_link_<n>_pump_us", "device_link_<n>_dispatch_interval_us",
        "device_link_<n>_inflight_at_dispatch",
    ])
    def test_device_path_name_is_documented(self, name):
        with open(registry_lint.OBSERVABILITY_MD) as fh:
            assert f"`{name}`" in fh.read()

    @pytest.mark.parametrize("doc", [
        "README.md", "docs/ANALYSIS.md", "docs/DEVICE_PLANE.md",
        "docs/OBSERVABILITY.md", "docs/PARITY.md", "docs/ROBUSTNESS.md",
    ])
    def test_document_names_only_files_of_the_tree(self, doc):
        assert os.path.join(REPO, doc) in registry_lint.doc_paths()
        vs = registry_lint.check_doc_files([os.path.join(REPO, doc)])
        assert not vs, _fmt(vs)

    def test_document_naming_a_missing_file_flagged(self, tmp_path):
        doc = tmp_path / "DOC.md"
        doc.write_text(
            "`rpc/server.py:315` and `python3 chip_smoke.py --rehearse-on-cpu`\n"
            "and `README.md` are here; `retired_script.py:7,9-12` is not,\n"
            "nor is `docs/server.py`; `tbnet.cc` and out.json are not asked.\n"
        )
        vs = registry_lint.check_doc_files([str(doc)])
        assert [(v.rule, v.line) for v in vs] == [
            ("doc-file-missing", 2), ("doc-file-missing", 3)], _fmt(vs)
        assert "retired_script.py" in vs[0].message
        assert "docs/server.py" in vs[1].message


# ---------------------------------------------------------------------------
# 3. sanitizer harness (slow; probe-gated like the multiprocess tiers)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestSanitizers:
    def test_asan_ubsan_native_subset(self):
        from tools.fabriclint import san

        ok, detail = san.probe("asan")
        if not ok:
            pytest.skip(f"asan unsupported here: {detail}")
        assert san.run_asan() == 0

    def test_tsan_ring_stress(self):
        from tools.fabriclint import san

        ok, detail = san.probe("tsan")
        if not ok:
            pytest.skip(f"tsan unsupported here: {detail}")
        assert san.run_tsan() == 0


# ===========================================================================
# fabricverify — lock-order, lifecycle, and state-machine verification
# (tools/fabricverify; sibling of fabriclint, same annotation grammar)
# ===========================================================================

import ast
import json

from tools.fabriclint import to_records
from tools.fabricverify import run_all as verify_run_all
from tools.fabricverify import lifecycle, lockorder, modelcheck
from tools.fabricverify.models import BreakerModel, SessionModel


class TestFabricverifyClean:
    """The live tree is clean — these tests ARE the concurrency lint gate."""

    def test_lock_order_graph_is_acyclic(self):
        vs = lockorder.check()
        assert not vs, _fmt(vs)

    def test_lifecycle_balance(self):
        vs = lifecycle.check()
        assert not vs, _fmt(vs)

    def test_protocol_models_hold(self):
        vs = modelcheck.check()
        assert not vs, _fmt(vs)

    def test_run_all_aggregate(self):
        vs = verify_run_all()
        assert not vs, _fmt(vs)


class TestLockCoverage:
    """The acceptance contract: every threading.Lock/RLock/Condition
    construction site in incubator_brpc_tpu/ is modeled, allowlist-free."""

    @pytest.fixture(scope="class")
    def analysis(self):
        return lockorder.analyze()

    def test_every_lock_site_modeled(self, analysis):
        # independent count: a plain AST scan with none of the analyzer's
        # binding machinery — the two must agree exactly
        expected = 0
        for path in lockorder.iter_pkg_files():
            with open(path) as fh:
                try:
                    tree = ast.parse(fh.read())
                except SyntaxError:
                    continue
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                fn = node.func
                if (
                    isinstance(fn, ast.Attribute)
                    and isinstance(fn.value, ast.Name)
                    and fn.value.id == "threading"
                    and fn.attr in ("Lock", "RLock", "Condition",
                                    "Semaphore", "BoundedSemaphore")
                ):
                    expected += 1
        modeled = sum(len(m.sites) for m in analysis.modules.values())
        unmodeled = sum(len(m.unmodeled) for m in analysis.modules.values())
        assert unmodeled == 0, "unbound lock construction sites exist"
        assert modeled == expected and expected > 80, (
            f"analyzer modeled {modeled} of {expected} lock sites"
        )
        # allowlist-free: no lock-unmodeled exemptions anywhere in the pkg
        for path in lockorder.iter_pkg_files():
            with open(path) as fh:
                src = fh.read()
            ann = scan_annotations(path, src)
            for allows in ann.allows.values():
                assert not any(r == "lock-unmodeled" for r, _ in allows), (
                    f"{path}: lock-unmodeled allowlisted"
                )

    def test_condition_wraps_lock_as_alias(self, analysis):
        # Server._quiescent = Condition(Server._lock): one entity, so a
        # Condition wait is correctly modeled as holding the lock
        e = analysis.entities.get("rpc/server.Server._quiescent")
        assert e is not None and e.alias_of == "rpc/server.Server._lock"

    def test_known_nesting_edges_found(self, analysis):
        # ground truth spot checks: nesting that exists in the code today
        keys = set(analysis.edges)
        assert (
            "rpc/server.Server._session_lock",
            "rpc/data_pool.SimpleDataPool._lock",
        ) in keys  # session_local_data borrows under the session lock
        assert (
            "lb/__init__.LoadBalancerWithNaming._cb_lock",
            "rpc/circuit_breaker._BreakerRegistry._lock",
        ) in keys  # _breaker registers inside the cb lock

    def test_hierarchy_doc_in_sync(self, analysis):
        generated = lockorder.render_hierarchy(analysis).strip()
        documented = lockorder.documented_hierarchy()
        assert generated == documented, (
            "docs/ANALYSIS.md lock hierarchy is stale — run "
            "`python -m tools.fabricverify --write-docs`"
        )


_CYCLE_SRC = '''
import threading

class A:
    def __init__(self):
        self._alpha_lock = threading.Lock()
        self._beta_lock = threading.Lock()

    def ab(self):
        with self._alpha_lock:
            with self._beta_lock:
                pass

    def ba(self):
        with self._beta_lock:
            with self._alpha_lock:
                pass
'''

_CALL_CYCLE_SRC = '''
import threading

class B:
    def __init__(self):
        self._front_lock = threading.Lock()
        self._back_lock = threading.Lock()

    def _touch_back(self):
        with self._back_lock:
            pass

    def front_then_back(self):
        with self._front_lock:
            self._touch_back()       # front -> back, through the call graph

    def _touch_front(self):
        with self._front_lock:
            pass

    def back_then_front(self):
        with self._back_lock:
            self._touch_front()      # back -> front: the cycle
'''

_SELF_REACQUIRE_SRC = '''
import threading

class C:
    def __init__(self):
        self._mono_lock = threading.Lock()

    def _inner(self):
        with self._mono_lock:
            pass

    def outer(self):
        with self._mono_lock:
            self._inner()            # non-reentrant Lock re-acquired: deadlock
'''


class TestLockOrderMeta:
    """Seeded violations flip the pass red (the meta-tests)."""

    def _check(self, tmp_path, src):
        p = tmp_path / "m.py"
        p.write_text(src)
        return lockorder.check([str(p)])

    def test_opposite_order_cycle_flips_red(self, tmp_path):
        vs = self._check(tmp_path, _CYCLE_SRC)
        assert any(v.rule == "lock-cycle" for v in vs), _fmt(vs)
        msg = next(v.message for v in vs if v.rule == "lock-cycle")
        assert "_alpha_lock" in msg and "_beta_lock" in msg

    def test_cycle_through_call_graph_flips_red(self, tmp_path):
        vs = self._check(tmp_path, _CALL_CYCLE_SRC)
        assert any(v.rule == "lock-cycle" for v in vs), _fmt(vs)

    def test_self_reacquisition_through_call_flips_red(self, tmp_path):
        vs = self._check(tmp_path, _SELF_REACQUIRE_SRC)
        assert any(
            v.rule == "lock-cycle" and "_mono_lock" in v.message for v in vs
        ), _fmt(vs)

    def test_allow_breaks_the_edge(self, tmp_path):
        src = _CYCLE_SRC.replace(
            "        with self._beta_lock:\n            with self._alpha_lock:",
            "        with self._beta_lock:\n"
            "            # fabriclint: allow(lock-cycle) proven safe: ba() "
            "only runs single-threaded at init\n"
            "            with self._alpha_lock:",
        )
        vs = self._check(tmp_path, src)
        assert not [v for v in vs if v.rule == "lock-cycle"], _fmt(vs)

    def test_unbindable_ctor_is_unmodeled(self, tmp_path):
        vs = self._check(
            tmp_path,
            "import threading\ndef f(q):\n    q.put(threading.Lock())\n",
        )
        assert any(v.rule == "lock-unmodeled" for v in vs), _fmt(vs)


_BORROW_LEAK_SRC = '''
class H:
    def grab(self):
        obj = self._pool.borrow()
        return obj.size          # never given back, never stored
'''

_BORROW_OK_LOCAL_SRC = '''
class H:
    def use(self):
        obj = self._pool.borrow()
        try:
            return obj.size
        finally:
            self._pool.give_back(obj)
'''

_BORROW_OK_STORED_SRC = '''
class H:
    def attach(self, ctx):
        obj = self._pool.borrow()
        ctx["_data"] = obj

    def detach(self, ctx):
        data = ctx.pop("_data", None)
        if data is not None:
            self._pool.give_back(data)
'''

_TIMER_DISCARD_SRC = '''
class H:
    def arm(self, timer):
        timer.schedule(self._tick, delay=1.0)
'''

_TIMER_OK_SRC = '''
class H:
    def arm(self, timer):
        self._tid = timer.schedule(self._tick, delay=1.0)

    def stop(self, timer):
        timer.unschedule(self._tid)
'''

_HOOK_LEAK_SRC = '''
class H:
    def watch(self, sock):
        sock.on_failed.append(self._on_fail)
'''

_HOOK_OK_SRC = '''
class H:
    def watch(self, sock):
        sock.on_failed.append(self._on_fail)

    def unwatch(self, sock):
        sock.on_failed.remove(self._on_fail)
'''


class TestLifecycleMeta:
    def _check(self, tmp_path, src):
        p = tmp_path / "m.py"
        p.write_text(src)
        return lifecycle.check([str(p)])

    def test_missing_give_back_flips_red(self, tmp_path):
        vs = self._check(tmp_path, _BORROW_LEAK_SRC)
        assert [v.rule for v in vs] == ["lifecycle-borrow"], _fmt(vs)

    def test_local_give_back_passes(self, tmp_path):
        assert not self._check(tmp_path, _BORROW_OK_LOCAL_SRC)

    def test_stored_borrow_with_teardown_passes(self, tmp_path):
        assert not self._check(tmp_path, _BORROW_OK_STORED_SRC)

    def test_ownership_transfer_annotation(self, tmp_path):
        src = _BORROW_LEAK_SRC.replace(
            "        obj = self._pool.borrow()",
            "        # fabriclint: allow(lifecycle-borrow) caller owns it; "
            "died-connection teardown gives it back\n"
            "        obj = self._pool.borrow()",
        )
        assert not self._check(tmp_path, src)

    def test_missing_unschedule_flips_red(self, tmp_path):
        vs = self._check(tmp_path, _TIMER_DISCARD_SRC)
        assert [v.rule for v in vs] == ["lifecycle-timer"], _fmt(vs)

    def test_stored_id_with_unschedule_passes(self, tmp_path):
        assert not self._check(tmp_path, _TIMER_OK_SRC)

    def test_stored_id_without_unschedule_flips_red(self, tmp_path):
        src = _TIMER_OK_SRC.replace(
            "    def stop(self, timer):\n"
            "        timer.unschedule(self._tid)\n",
            "",
        )
        vs = self._check(tmp_path, src)
        assert [v.rule for v in vs] == ["lifecycle-timer"], _fmt(vs)

    def test_hook_without_removal_flips_red(self, tmp_path):
        vs = self._check(tmp_path, _HOOK_LEAK_SRC)
        assert [v.rule for v in vs] == ["lifecycle-callback"], _fmt(vs)

    def test_hook_with_removal_passes(self, tmp_path):
        assert not self._check(tmp_path, _HOOK_OK_SRC)

    def test_observer_without_removal_flips_red(self, tmp_path):
        vs = self._check(
            tmp_path,
            "class H:\n"
            "    def start(self, ns):\n"
            "        ns.add_observer(self)\n",
        )
        assert [v.rule for v in vs] == ["lifecycle-callback"], _fmt(vs)

    def test_observer_with_removal_passes(self, tmp_path):
        assert not self._check(
            tmp_path,
            "class H:\n"
            "    def start(self, ns):\n"
            "        ns.add_observer(self)\n"
            "    def stop(self, ns):\n"
            "        ns.remove_observer(self)\n",
        )


class TestModelChecker:
    def test_session_space_is_exhaustive(self):
        # the acceptance scope: 3 parties, 2 steps, reorder + 1 drop +
        # 1 duplicate — a real state space, not a toy walk
        res = modelcheck.explore(SessionModel(n_parties=3, steps=2,
                                              floors=(0, 1, 3)))
        assert not res.violations, _fmt(res.violations)
        assert res.states > 1000 and res.transitions > res.states

    def test_breaker_machine_fully_covered(self):
        from tools.fabricverify.models import (
            B_CLOSED, B_HALF_OPEN, B_ISOLATED,
        )

        res = modelcheck.explore(BreakerModel())
        assert not res.violations, _fmt(res.violations)
        modes = {s[0] for s in res.parent}
        levels = {s[1] for s in res.parent}
        assert modes == {B_CLOSED, B_ISOLATED, B_HALF_OPEN}
        assert levels == {1, 2, 4, 8}  # every doubling level reached

    # -- the seeded protocol mutations (acceptance criteria) --------------

    def test_dropped_close_echo_flips_red(self):
        res = modelcheck.explore(SessionModel(drop_close_echo=True))
        assert any(v.rule == "model-stuck" for v in res.violations), (
            _fmt(res.violations)
        )

    def test_non_monotone_join_flips_red(self):
        res = modelcheck.explore(SessionModel(min_join=True))
        assert any(v.rule == "model-unsafe" for v in res.violations), (
            _fmt(res.violations)
        )

    def test_silent_floor_violation_flips_red(self):
        res = modelcheck.explore(
            SessionModel(min_join=True, no_floor_reject=True)
        )
        assert any(
            v.rule == "model-unsafe" and "floor" in v.message
            for v in res.violations
        ), _fmt(res.violations)

    # -- the fault plane (party death + abort convergence) ----------------

    def test_party_death_scope_holds(self):
        """The shipped fault scope: one party may die at any instant —
        every reachable terminal state leaves no LIVING party stuck in
        the lockstep barrier (the abort broadcast + detection converge)."""
        res = modelcheck.explore(
            SessionModel(
                n_parties=3, steps=2, floors=(0, 1, 3), max_deaths=1
            )
        )
        assert not res.violations, _fmt(res.violations)
        assert res.states > 10_000  # a real fault space, not a toy walk

    def test_dropped_abort_broadcast_flips_red(self):
        """The acceptance meta-test: a proposer that aborts without
        broadcasting leaves survivors wedged in the barrier — the
        abort-convergence check names the stuck party."""
        res = modelcheck.explore(
            SessionModel(max_deaths=1, drop_abort=True)
        )
        assert any(
            v.rule == "model-unsafe"
            and "stuck in the lockstep barrier" in v.message
            for v in res.violations
        ), _fmt(res.violations)

    def test_default_models_cover_party_death(self):
        """make verify-models runs the extended scope by default."""
        names = [m.name for m in modelcheck.default_models()]
        assert "mc_dispatch_session_party_death" in names

    def test_unrevivable_breaker_flips_red(self):
        res = modelcheck.explore(BreakerModel(reset_keeps_broken=True))
        assert any(
            v.rule == "model-unrevivable" for v in res.violations
        ), _fmt(res.violations)

    def test_missing_revive_timer_deadlocks(self):
        res = modelcheck.explore(BreakerModel(no_revive_timer=True))
        assert any(v.rule == "model-stuck" for v in res.violations), (
            _fmt(res.violations)
        )

    def test_unreset_duration_flips_red(self):
        res = modelcheck.explore(BreakerModel(no_duration_reset=True))
        assert any(v.rule == "model-unsafe" for v in res.violations), (
            _fmt(res.violations)
        )

    # -- the resume scope (elastic sessions: checkpoint/resume/replace) ----

    def test_resume_scope_explores_exhaustively(self):
        """The acceptance scope: step-granular progress, nondeterministic
        per-party checkpointing, ≤1 death + ≤1 drop, the resume barrier
        and the replacement join — exhaustively clean and well past 10k
        states."""
        from tools.fabricverify.models import ResumeSessionModel

        res = modelcheck.explore(ResumeSessionModel(n_parties=3, steps=2))
        assert not res.violations, _fmt(res.violations)
        assert res.states > 10_000, res.states

    def test_default_models_cover_resume_scope(self):
        """make verify-models runs (and prints the state count of) the
        resume scope by default."""
        names = [m.name for m in modelcheck.default_models()]
        assert "mc_dispatch_session_resume" in names

    def test_max_resume_join_flips_red(self):
        """Folding survivor watermarks with max instead of min elects a
        resume point some survivor never checkpointed."""
        from tools.fabricverify.models import ResumeSessionModel

        res = modelcheck.explore(ResumeSessionModel(max_resume_join=True))
        assert any(
            v.rule == "model-unsafe" and "min-join" in v.message
            for v in res.violations
        ), _fmt(res.violations)

    def test_skip_replacement_flips_red(self):
        """Resuming without filling the dead slot re-runs steps with a
        divergent party set — silently different math for axis-reducing
        kernels."""
        from tools.fabricverify.models import ResumeSessionModel

        res = modelcheck.explore(ResumeSessionModel(skip_replacement=True))
        assert any(
            v.rule == "model-unsafe" and "divergent party set" in v.message
            for v in res.violations
        ), _fmt(res.violations)

    def test_no_resume_timeout_deadlocks(self):
        """A resume barrier without a drop backstop wedges the proposer
        forever on one lost query/ack."""
        from tools.fabricverify.models import ResumeSessionModel

        res = modelcheck.explore(ResumeSessionModel(no_resume_timeout=True))
        assert any(v.rule == "model-stuck" for v in res.violations), (
            _fmt(res.violations)
        )

    # -- the overlap scope (chunked double-buffered sessions, T3) ---------

    def test_overlap_scope_explores_exhaustively(self):
        """The shipped scope: chunk-granular dispatch/ack pipelines per
        party, two-slot double buffer, per-chunk collective rendezvous,
        ≤1 death + ≤1 drop (including mid-step with half a step's chunks
        acked) — exhaustively clean and well past 10k states."""
        from tools.fabricverify.models import OverlapSessionModel

        res = modelcheck.explore(
            OverlapSessionModel(n_parties=3, steps=3, chunks=3)
        )
        assert not res.violations, _fmt(res.violations)
        assert res.states > 10_000, res.states

    def test_default_models_cover_overlap_scope(self):
        """make verify-models runs (and prints the state count of) the
        overlap scope by default."""
        names = [m.name for m in modelcheck.default_models()]
        assert "mc_dispatch_session_overlap" in names

    def test_ack_before_chunk_complete_flips_red(self):
        """A chunk acked at dispatch time (before its sub-collective
        completed) witnesses nothing — the ack discipline is violated."""
        from tools.fabricverify.models import OverlapSessionModel

        res = modelcheck.explore(
            OverlapSessionModel(ack_before_complete=True)
        )
        assert any(
            v.rule == "model-unsafe"
            and "before the sub-collective completed" in v.message
            for v in res.violations
        ), _fmt(res.violations)

    def test_dispatch_before_predecessor_ack_flips_red(self):
        """Dispatching step k+1's slice j before step k's chunk j was
        acked puts more than two step slots in flight on one slice —
        the double-buffer window invariant."""
        from tools.fabricverify.models import OverlapSessionModel

        res = modelcheck.explore(OverlapSessionModel(no_ack_gate=True))
        assert any(
            v.rule == "model-unsafe"
            and "more than two step slots in flight" in v.message
            for v in res.violations
        ), _fmt(res.violations)

    def test_overlap_death_mid_step_converges(self):
        """Death during a half-acked step: every terminal state of the
        fault scope leaves no living party wedged in its chunk pipeline
        (the abort reaches everyone) — asserted by the clean explore,
        and the death branch is genuinely exercised."""
        from tools.fabricverify.models import OverlapSessionModel

        res = modelcheck.explore(
            OverlapSessionModel(n_parties=2, steps=2, chunks=2)
        )
        assert not res.violations, _fmt(res.violations)
        died = [
            lbl for _s, (_p, lbl) in res.parent.items()
            if lbl.startswith("die")
        ]
        assert died, "the death environment action was never explored"

    def test_counterexample_traces_attached(self):
        res = modelcheck.explore(SessionModel(drop_close_echo=True))
        v = next(v for v in res.violations if v.rule == "model-stuck")
        assert "trace:" in v.message and "deliver" in v.message

    def test_standalone_cli_reports_state_counts(self, capsys):
        assert modelcheck.main([]) == 0
        out = capsys.readouterr().out
        assert "mc_dispatch_session" in out and "states" in out
        assert "circuit_breaker" in out


class TestJsonReports:
    """--json: {rule, file, line, reason} records, diffable across commits."""

    def test_record_schema(self):
        from tools.fabriclint import Violation

        recs = to_records(
            [Violation("lock-cycle", os.path.join(REPO, "x/y.py"), 7, "boom")]
        )
        assert recs == [
            {"rule": "lock-cycle", "file": "x/y.py", "line": 7,
             "reason": "boom"}
        ]

    def test_fabriclint_json_clean(self, capsys):
        from tools.fabriclint.__main__ import main as lint_main

        assert lint_main(["--json"]) == 0
        assert json.loads(capsys.readouterr().out) == []

    def test_fabricverify_json_clean(self, capsys):
        from tools.fabricverify.__main__ import main as verify_main

        assert verify_main(["--json"]) == 0
        assert json.loads(capsys.readouterr().out) == []

    def test_verify_rules_registered_in_shared_grammar(self):
        # one scanner validates every allow(): fabricverify's ids must be
        # in fabriclint.RULES or its exemptions would be bad-allow
        from tools.fabricverify import RULES as VRULES

        assert set(VRULES) <= set(RULES)


# ---------------------------------------------------------------------------
# fabricscan — C++-plane static analysis (tools/fabricscan; third sibling,
# same annotation grammar: wire-bounds taint dataflow, reactor-ownership
# checking, cross-plane parity lint)
# ---------------------------------------------------------------------------

from tools.fabricscan import cmodel as scan_cmodel
from tools.fabricscan import ownership as scan_ownership
from tools.fabricscan import parity as scan_parity
from tools.fabricscan import wirebounds as scan_wirebounds
from tools.fabricscan import run_all as scan_run_all


@pytest.fixture(scope="module")
def tbnet_cc_text():
    with open(os.path.join(REPO, "src", "tbnet", "tbnet.cc")) as fh:
        return fh.read()


def _mutate_cc(text, old, new):
    assert old in text, f"mutation anchor missing: {old!r}"
    mutated = text.replace(old, new)
    assert mutated != text
    return mutated


class TestScanRepoIsClean:
    """The live C++ tree passes all three passes — this IS the lint gate
    for src/tbnet + src/tbutil (the same run as `make lint`)."""

    def test_wire_bounds_clean(self):
        vs = scan_wirebounds.check()
        assert not vs, _fmt(vs)

    def test_ownership_clean(self):
        vs = scan_ownership.check()
        assert not vs, _fmt(vs)

    def test_plane_parity_clean(self):
        vs = scan_parity.check()
        assert not vs, _fmt(vs)

    def test_run_all_aggregate(self):
        vs = scan_run_all()
        assert not vs, _fmt(vs)

    def test_fabricscan_json_clean(self, capsys):
        from tools.fabricscan.__main__ import main as scan_main

        assert scan_main(["--json"]) == 0
        assert json.loads(capsys.readouterr().out) == []

    def test_scan_rules_registered_in_shared_grammar(self):
        # one scanner validates every allow(): fabricscan's ids must be
        # in fabriclint.RULES or its exemptions would be bad-allow
        from tools.fabricscan import RULES as SRULES

        assert set(SRULES) <= set(RULES)


class TestScanCoverageIsAllowlistFree:
    """ISSUE 12 acceptance: the analysis covers what it claims to cover,
    with NO allow() escape hatches on the checked surfaces."""

    @pytest.fixture(scope="class")
    def model(self):
        return scan_cmodel.parse_native_plane()

    def test_cpp_model_parses_everything(self, model):
        # the cdecl discipline lifted to bodies: an unparsed definition
        # is an unchecked definition
        assert model.unparsed == []

    def test_cutter_call_graph_is_visited(self, model):
        # every wire-bounds root resolves, and the closure reaches the
        # functions the frame path actually rides — scanner, codec
        # table, tbus header pair, varint reader
        for root in scan_wirebounds.ROOTS:
            assert root in model.funcs, f"root {root} vanished"
        reach = scan_wirebounds.checked_functions(model)
        for expected in (
            "process_frames", "scan_prpc_meta", "prpc_peek", "read_varint",
            "codec_decompress", "snappy_decompress_block", "zlib_decompress",
            "tb_tbus_peek", "tb_tbus_cut", "run_native",
            "tb_channel_pump", "pump_once", "prpc_complete_one",
            "tb_scan_prpc_meta",
        ):
            assert expected in reach, f"{expected} fell out of the checked"\
                " call graph"

    def test_netloop_netconn_fields_all_owned(self, model):
        # every mutable NetLoop/NetConn field carries a declared owner —
        # the multi-reactor structures are fully covered, not sampled
        for sname in ("NetLoop", "NetConn"):
            owned = scan_ownership.owned_fields(model, sname)
            assert owned, f"{sname} lost its fields"
            missing = [f for f, o in owned.items() if o is None]
            assert not missing, f"{sname} fields without owners: {missing}"

    def test_checked_structs_all_owned(self, model):
        # the wider claim: every mutable field on every checked struct
        missing = []
        for sname in scan_ownership.CHECKED_STRUCTS:
            for f, o in scan_ownership.owned_fields(model, sname).items():
                if o is None:
                    missing.append(f"{sname}.{f}")
        assert not missing, missing

    def test_no_scan_rule_allowlisted_in_cpp(self):
        # allowlist-free: fixes, not exemptions (the PR 6/7 discipline) —
        # no allow() for any fabricscan rule anywhere in the C++ plane
        from tools.fabricscan import RULES as SRULES

        for path in (scan_cmodel.TBNET_CC, scan_cmodel.TBUTIL_CC):
            anns = scan_annotations(path)
            allowed_scan = [
                (line, rule)
                for line, items in anns.allows.items()
                for rule, _reason in items
                if rule in SRULES
            ]
            assert not allowed_scan, (
                f"{path}: fabricscan violations must be fixed, not "
                f"allowlisted: {allowed_scan}"
            )


class TestWireBoundsCatchesMutations:
    """Seeded mutations flip wire-bounds red (≥2 per ISSUE 12)."""

    def test_dropped_pump_frame_cap(self, tbnet_cc_text):
        # the guard the pass found missing at introduction: without the
        # client-side cap a hostile tbus body_len grows rbuf unbounded
        mut = _mutate_cc(
            tbnet_cc_text,
            " ||\n            hdr.body_len > kClientMaxBody",
            "",
        )
        vs = scan_wirebounds.check(tbnet_text=mut)
        assert any(
            v.rule == "wire-bounds" and "tb_channel_pump" in v.message
            and "hdr.body_len" in v.message
            for v in vs
        ), _fmt(vs)

    def test_dropped_submessage_length_guard(self, tbnet_cc_text):
        # the scanner's `len > n - off` subtraction idiom removed: the
        # tainted submessage length reaches read_varint's bound unguarded
        mut = _mutate_cc(
            tbnet_cc_text,
            "if (!read_varint(p, n, &off, &len) || len > n - off) return m;",
            "if (!read_varint(p, n, &off, &len)) return m;",
        )
        vs = scan_wirebounds.check(tbnet_text=mut)
        assert any(
            v.rule == "wire-bounds" and "scan_prpc_meta" in v.message
            and "sub_len" in v.message
            for v in vs
        ), _fmt(vs)

    def test_dropped_snappy_table_mask(self, tbnet_cc_text):
        # the hash-table subscript loses its explicit cap: the value
        # loaded out of the input buffer indexes slots unguarded
        mut = _mutate_cc(
            tbnet_cc_text,
            "    h &= kSnappyTableMask;",
            "",
        )
        vs = scan_wirebounds.check(tbnet_text=mut)
        assert any(
            v.rule == "wire-bounds" and "snappy_compress_block" in v.message
            for v in vs
        ), _fmt(vs)


class TestOwnershipCatchesMutations:
    """Seeded mutations flip ownership/owner-missing red (≥2)."""

    def test_stripped_owner_annotation(self, tbnet_cc_text):
        # unannotated mutable shared state is itself a violation
        mut = _mutate_cc(
            tbnet_cc_text,
            "int inline_burst = 0;  // fabricscan: owner(loop)",
            "int inline_burst = 0;",
        )
        vs = scan_ownership.check(tbnet_text=mut)
        assert any(
            v.rule == "owner-missing" and "inline_burst" in v.message
            for v in vs
        ), _fmt(vs)

    def test_loop_owned_field_written_from_python_role(self, tbnet_cc_text):
        # a loop-owned field touched from a Python-caller export without
        # an atomic/ring/lock — PR 9's invariant, checked
        mut = _mutate_cc(
            tbnet_cc_text,
            "int tb_server_num_reactors(const tb_server* s) {\n"
            "  return static_cast<int>(s->loops.size());",
            "int tb_server_num_reactors(const tb_server* s) {\n"
            "  s->loops[0]->inline_burst = 0;\n"
            "  return static_cast<int>(s->loops.size());",
        )
        vs = scan_ownership.check(tbnet_text=mut)
        assert any(
            v.rule == "ownership" and "inline_burst" in v.message
            and "tb_server_num_reactors" in v.message
            for v in vs
        ), _fmt(vs)

    def test_setter_losing_init_seed_flips_red(self, tbnet_cc_text):
        # init-owned = write-once setup: a pre-listen setter that loses
        # its role(init) seed becomes an arbitrary-Python-thread export
        # writing an init-owned field — flagged
        mut = _mutate_cc(
            tbnet_cc_text,
            "// fabricscan: role(init)\n"
            "void tb_server_set_max_body",
            "void tb_server_set_max_body",
        )
        vs = scan_ownership.check(tbnet_text=mut)
        assert any(
            v.rule == "ownership" and "tb_server.max_body" in v.message
            and "tb_server_set_max_body" in v.message
            for v in vs
        ), _fmt(vs)


class TestPlaneParityCatchesMutations:
    """Seeded constant drift between the twins flips plane-parity red
    (≥2): wire numbers, enum ids, error texts, codec constants."""

    def test_skewed_rpc_meta_field_number(self, tbnet_cc_text):
        mut = _mutate_cc(
            tbnet_cc_text,
            "} else if (field == 4) {\n        m.cid = v;",
            "} else if (field == 6) {\n        m.cid = v;",
        )
        vs = scan_parity.check(tbnet_text=mut)
        assert any(
            v.rule == "plane-parity" and "correlation_id" in v.message
            for v in vs
        ), _fmt(vs)

    def test_skewed_codec_enum_id(self, tbnet_cc_text):
        mut = _mutate_cc(
            tbnet_cc_text,
            "constexpr uint32_t kCompressGzip = 2;",
            "constexpr uint32_t kCompressGzip = 4;",
        )
        vs = scan_parity.check(tbnet_text=mut)
        assert any(
            v.rule == "plane-parity" and "gzip" in v.message for v in vs
        ), _fmt(vs)

    def test_skewed_berror_text(self, tbnet_cc_text):
        mut = _mutate_cc(
            tbnet_cc_text,
            'kDeadlineShedText[] = "',
            'kDeadlineShedText[] = "x',
        )
        vs = scan_parity.check(tbnet_text=mut)
        assert any(
            v.rule == "plane-parity" and "EDEADLINE" in v.message
            for v in vs
        ), _fmt(vs)

    def test_skewed_snappy_hash_multiplier(self, tbnet_cc_text):
        mut = _mutate_cc(tbnet_cc_text, "0x1E35A7BDu", "0x1E35A7BFu")
        vs = scan_parity.check(tbnet_text=mut)
        assert any(
            v.rule == "plane-parity" and "hash multiplier" in v.message
            for v in vs
        ), _fmt(vs)

    def test_skewed_trace_decode_field_number(self, tbnet_cc_text):
        # ISSUE 15: the cutter decoding trace_id from the wrong
        # RpcRequestMeta field would silently break every distributed
        # trace — the decode-side anchor flips red
        mut = _mutate_cc(
            tbnet_cc_text,
            "} else if (f2 == 4) {  // trace_id: the caller's trace",
            "} else if (f2 == 14) {  // trace_id: the caller's trace",
        )
        vs = scan_parity.check(tbnet_text=mut)
        assert any(
            v.rule == "plane-parity" and "trace_id" in v.message
            for v in vs
        ), _fmt(vs)

    def test_skewed_sampled_bit_field_number(self, tbnet_cc_text):
        mut = _mutate_cc(
            tbnet_cc_text,
            "} else if (f2 == 9) {  // head-based sampled bit (extension)",
            "} else if (f2 == 7) {  // head-based sampled bit (extension)",
        )
        vs = scan_parity.check(tbnet_text=mut)
        assert any(
            v.rule == "plane-parity" and "traced_sampled" in v.message
            for v in vs
        ), _fmt(vs)

    def test_skewed_traced_pump_pack_tag(self, tbnet_cc_text):
        # pack side: the traced pump template stamping log_id under the
        # wrong tag byte (field 7 instead of 3) must flip red against
        # encode_request_submeta's field table
        mut = _mutate_cc(
            tbnet_cc_text,
            "t[o++] = 0x18;  // RpcRequestMeta.log_id (field 3)",
            "t[o++] = 0x38;  // RpcRequestMeta.log_id (field 3)",
        )
        vs = scan_parity.check(tbnet_text=mut)
        assert any(
            v.rule == "plane-parity"
            and "traced pump-template field number of log_id" in v.message
            for v in vs
        ), _fmt(vs)

    def test_skewed_telemetry_record_size_anchor(self, tbnet_cc_text):
        # the 48 -> 64 byte record growth, pinned: one side's size
        # constant left behind flips the parity anchor red
        mut = _mutate_cc(
            tbnet_cc_text,
            "static_assert(sizeof(tb_telemetry_record) == 64,",
            "static_assert(sizeof(tb_telemetry_record) == 48,",
        )
        vs = scan_parity.check(tbnet_text=mut)
        assert any(
            v.rule == "plane-parity"
            and "telemetry record ABI bytes" in v.message
            for v in vs
        ), _fmt(vs)

    def test_refactored_anchor_screams_not_silently_passes(self,
                                                           tbnet_cc_text):
        # extraction anchors are load-bearing: refactoring a constant out
        # from under its regex must fail loudly (scan-parse), never
        # silently compare nothing
        mut = _mutate_cc(
            tbnet_cc_text,
            "constexpr uint32_t kMagicPrpc = ",
            "constexpr uint32_t kMagicPrpcRenamed = ",
        )
        vs = scan_parity.check(tbnet_text=mut)
        assert any(
            v.rule == "scan-parse" and "PRPC magic" in v.message
            for v in vs
        ), _fmt(vs)


class TestFfiCountIsGenerated:
    """ISSUE 12 satellite: the FFI surface size quoted in the docs is
    generated from native.SIGNATURES, not hand-kept prose — the number
    in PARITY row 53 can't rot."""

    def test_parity_row_53_count_matches_signatures(self):
        from incubator_brpc_tpu import native

        n = len(native.SIGNATURES)
        with open(os.path.join(REPO, "docs", "PARITY.md")) as fh:
            parity_text = fh.read()
        assert f"{n} functions" in parity_text, (
            f"docs/PARITY.md row 53 must quote the generated count "
            f"({n} functions == len(native.SIGNATURES))"
        )
        # and no stale hand-kept count survives
        import re as _re

        for m in _re.finditer(r"(?<![~\d])(\d+) functions", parity_text):
            assert int(m.group(1)) == n, (
                f"stale FFI count {m.group(0)!r} in docs/PARITY.md "
                f"(SIGNATURES has {n})"
            )

    def test_analysis_md_count_matches_signatures(self):
        from incubator_brpc_tpu import native

        n = len(native.SIGNATURES)
        with open(os.path.join(REPO, "docs", "ANALYSIS.md")) as fh:
            text = fh.read()
        import re as _re

        for m in _re.finditer(r"(?<![~\d])(\d+) functions", text):
            assert int(m.group(1)) == n, (
                f"stale FFI count {m.group(0)!r} in docs/ANALYSIS.md "
                f"(SIGNATURES has {n})"
            )
