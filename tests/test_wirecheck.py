"""wirecheck (dlrover_tpu/lint/wirecheck.py, docs/design/wirecheck.md):
wire & durable-format schema registry, skew rules, golden corpus — plus
the typed unknown-message path through serde/policy/transport, the
versioned-format helper, and the skew shim."""

import dataclasses
import json
import os

import pytest

from dlrover_tpu.common import messages as msg
from dlrover_tpu.common import serde, versioned_format
from dlrover_tpu.common.serde import UnknownMessageError
from dlrover_tpu.lint import wirecheck
from dlrover_tpu.lint.skew_shim import SkewShim
from dlrover_tpu.rpc import policy as rpc_policy

# ---------------------------------------------------------------------------
# the repo gate: checked-in schema + corpus + AST all clean
# ---------------------------------------------------------------------------


def test_repo_wire_clean():
    """The tier-1 twin of the CI step: the tree's wire vocabulary
    matches the checked-in wire_schema.json, the golden corpus replays,
    and no WC rule fires anywhere in the package."""
    res = wirecheck.run()
    assert not res.failed, (
        [v.format() for v in res.violations],
        res.schema_drift,
        res.corpus_failures,
        res.errors,
    )


def test_roundtrip_every_registered_message():
    """The auto-generated property test: every registered message,
    synthesized from its own type hints, survives
    serialize->deserialize bit-exactly (re-encode equality)."""
    registry = wirecheck.message_registry()
    assert len(registry) >= 60  # the vocabulary is actually covered
    for name, cls in sorted(registry.items()):
        obj = wirecheck.synth_instance(cls, registry)
        wire = serde.serialize(obj)
        back = serde.deserialize(wire)
        assert type(back) is cls, name
        assert serde._encode(back) == json.loads(wire.decode()), name


# ---------------------------------------------------------------------------
# schema diff (WC005)
# ---------------------------------------------------------------------------


def _schema(fields, name="M"):
    return {"messages": {name: {"fields": fields}}, "durable": {}}


def test_diff_classifies_added_fields():
    base = _schema({"a": {"type": "int", "default": True}})
    safe = _schema({
        "a": {"type": "int", "default": True},
        "b": {"type": "str", "default": True},
    })
    lines = wirecheck.diff_schema(safe, base)
    assert len(lines) == 1 and "safe add" in lines[0]
    breaking = _schema({
        "a": {"type": "int", "default": True},
        "b": {"type": "str", "default": False},
    })
    lines = wirecheck.diff_schema(breaking, base)
    assert len(lines) == 1 and "WITHOUT a default" in lines[0]


def test_diff_catches_removal_type_change_and_lost_default():
    base = _schema({
        "a": {"type": "int", "default": True},
        "b": {"type": "str", "default": True},
    })
    cur = _schema({"a": {"type": "float", "default": False}})
    lines = wirecheck.diff_schema(cur, base)
    text = "\n".join(lines)
    assert "M.b removed" in text
    assert "type changed int -> float" in text
    assert "LOST its default" in text
    # two-sided: a stale baseline message fails too
    lines = wirecheck.diff_schema(
        {"messages": {}, "durable": {}}, base
    )
    assert any("removed" in ln for ln in lines)


def test_diff_catches_durable_version_bump():
    base = {"messages": {}, "durable": {"f": {"version": 2}}}
    cur = {"messages": {}, "durable": {"f": {"version": 3}}}
    lines = wirecheck.diff_schema(cur, base)
    assert len(lines) == 1 and "version changed 2 -> 3" in lines[0]


def test_fix_schema_marks_new_fields_on_existing_messages_guarded(tmp_path):
    path = str(tmp_path / "schema.json")
    old = {
        "messages": {
            "M": {"fields": {
                "a": {"type": "int", "default": True,
                      "skew_guarded": True, "note": "old mark"},
            }},
        },
        "durable": {}, "revision": 3, "history": [],
    }
    cur = _schema({
        "a": {"type": "int", "default": True},
        "b": {"type": "str", "default": True},
    })
    cur["messages"]["N"] = {
        "fields": {"x": {"type": "int", "default": True}}
    }
    data = wirecheck.write_schema(path, cur, old, note="adds b and N")
    m = data["messages"]["M"]["fields"]
    # old metadata preserved, the NEW field on the EXISTING message
    # auto-marked guarded (it postdates the baseline)
    assert m["a"]["skew_guarded"] and m["a"]["note"] == "old mark"
    assert m["b"]["skew_guarded"] is True
    # fields of a brand-new message are born-with, not guarded
    assert "skew_guarded" not in data["messages"]["N"]["fields"]["x"]
    assert data["revision"] == 4
    assert data["history"][-1]["note"] == "adds b and N"
    assert any("N added" in c for c in data["history"][-1]["changes"])


def test_guarded_field_names_skips_ambiguous():
    schema = {"messages": {
        "A": {"fields": {
            "x": {"type": "int", "default": True, "skew_guarded": True},
            "y": {"type": "int", "default": True, "skew_guarded": True},
        }},
        "B": {"fields": {
            "y": {"type": "int", "default": True},  # born-with in B
        }},
    }}
    names = wirecheck.guarded_field_names(schema)
    assert "x" in names
    assert "y" not in names  # guarded in A, baseline in B -> ambiguous


def test_skew_baseline_drops_reads_checked_in_schema():
    drops = wirecheck.skew_baseline_drops()
    # the historical skew-safe fields are recorded as the N-1 drop set
    assert "latest_round" in drops["NumNodesWaitingResponse"]
    assert "speculation_hint" in drops["NumNodesWaitingResponse"]
    assert drops["OverloadedResponse"] == ["max_interval_s"]
    assert "comm_links" in drops["GlobalStepReport"]


# ---------------------------------------------------------------------------
# golden corpus (WC006)
# ---------------------------------------------------------------------------


def test_corpus_detects_missing_dropped_and_unknown(tmp_path):
    corpus = str(tmp_path / "corpus")
    wirecheck.write_corpus(corpus)
    assert wirecheck.check_corpus(corpus) == []
    # a field the current class dropped: rename a corpus key to a name
    # the decoder does not know -> "dropped by decode"
    p = os.path.join(corpus, "msg.SimpleResponse.json")
    with open(p) as f:
        data = json.load(f)
    data["ancient_field"] = 1
    with open(p, "w") as f:
        json.dump(data, f)
    fails = wirecheck.check_corpus(corpus)
    assert any("dropped by decode" in x for x in fails)
    # a message removed from the registry entirely
    os.rename(
        os.path.join(corpus, "msg.SimpleResponse.json"),
        os.path.join(corpus, "msg.RetiredMessage.json"),
    )
    fails = wirecheck.check_corpus(corpus)
    assert any("no longer registered" in x for x in fails)
    assert any(
        "SimpleResponse has no corpus file" in x for x in fails
    )


def test_corpus_detects_decode_failure(tmp_path):
    corpus = str(tmp_path / "corpus")
    wirecheck.write_corpus(corpus)
    p = os.path.join(corpus, "msg.KVStoreSet.json")
    with open(p) as f:
        data = json.load(f)
    data["value"] = {"_t": "__bytes__", "hex": "zz-not-hex"}
    with open(p, "w") as f:
        json.dump(data, f)
    fails = wirecheck.check_corpus(corpus)
    assert any("DECODE FAILED" in x for x in fails)


def test_corpus_legacy_shard_ckpt_pin_decodes_forever():
    """The frozen version-less 5-element doing_meta artifact: the
    checked-in pin must decode with the fence filled as -1."""
    from dlrover_tpu.master.shard.dataset_manager import (
        DatasetShardCheckpoint,
    )

    path = os.path.join(
        wirecheck.DEFAULT_CORPUS_DIR,
        "durable.dataset_shard_ckpt.legacy.json",
    )
    with open(path) as f:
        data = json.load(f)
    assert "_v" not in data  # it IS the pre-versioning format
    assert len(data["doing_meta"][0]) == 5
    ckpt = DatasetShardCheckpoint.from_json(json.dumps(data))
    assert ckpt.doing_meta[0][5] == -1
    assert ckpt.completed_records == data["completed_records"]


def test_fix_corpus_never_rewrites_frozen_legacy_pins(tmp_path):
    corpus = str(tmp_path / "corpus")
    wirecheck.write_corpus(corpus)
    p = os.path.join(corpus, "durable.dataset_shard_ckpt.legacy.json")
    with open(p, "w") as f:
        f.write('{"frozen": "artifact"}')
    wirecheck.write_corpus(corpus)
    with open(p) as f:
        assert json.load(f) == {"frozen": "artifact"}


def test_corpus_flags_stale_durable_version(tmp_path):
    corpus = str(tmp_path / "corpus")
    wirecheck.write_corpus(corpus)
    p = os.path.join(corpus, "durable.state_speed.json")
    with open(p) as f:
        data = json.load(f)
    data["_v"] = 1  # corpus written before a (hypothetical) bump
    with open(p, "w") as f:
        json.dump(data, f)
    fails = wirecheck.check_corpus(corpus)
    assert any("regenerate the corpus" in x for x in fails)


# ---------------------------------------------------------------------------
# WC AST rules on fixtures
# ---------------------------------------------------------------------------


def _ast(tmp_path, source, schema=None):
    p = tmp_path / "fixture.py"
    p.write_text(source)
    return wirecheck.check_ast([str(p)], schema or {"messages": {}})


def test_wc001_defaultless_field_fires(tmp_path):
    bad = (
        "from dlrover_tpu.common.serde import message\n"
        "@message\n"
        "class Evil:\n"
        "    required: int\n"
        "    fine: int = 0\n"
    )
    v, errs = _ast(tmp_path, bad)
    assert not errs
    assert [x.rule for x in v] == ["WC001"]
    assert "Evil.required" in v[0].message


def test_wc001_quiet_with_defaults_and_on_plain_dataclasses(tmp_path):
    ok = (
        "import dataclasses\n"
        "from dlrover_tpu.common.serde import message\n"
        "@message\n"
        "class Fine:\n"
        "    a: int = 0\n"
        "    b: list = dataclasses.field(default_factory=list)\n"
        "@dataclasses.dataclass\n"
        "class NotWire:\n"
        "    required: int\n"  # not a @message class: not our business
    )
    v, errs = _ast(tmp_path, ok)
    assert not errs and not v


def test_wc002_plain_read_of_guarded_field(tmp_path):
    schema = {"messages": {"R": {"fields": {
        "new_field": {"type": "int", "default": True,
                      "skew_guarded": True},
    }}}}
    src = (
        "def f(resp, inputs):\n"
        "    a = resp.new_field\n"           # fires: wire base, plain
        "    b = getattr(resp, 'new_field', 0)\n"   # guarded: clean
        "    c = inputs.new_field\n"          # non-wire base: skipped
        "    d = resp.new_field()\n"          # method call: skipped
        "    return a, b, c, d\n"
    )
    v, errs = _ast(tmp_path, src, schema)
    assert not errs
    assert [x.rule for x in v] == ["WC002"]
    assert v[0].line == 2


def test_wc002_suppression_line_above(tmp_path):
    schema = {"messages": {"R": {"fields": {
        "new_field": {"type": "int", "default": True,
                      "skew_guarded": True},
    }}}}
    src = (
        "def f(resp):\n"
        "    # graftlint: disable=WC002\n"
        "    return resp.new_field\n"
    )
    v, _ = _ast(tmp_path, src, schema)
    assert not v


def test_wc003_unhandled_deserialize(tmp_path):
    bad = (
        "from dlrover_tpu.common.serde import deserialize\n"
        "def f(b):\n"
        "    return deserialize(b)\n"
    )
    v, _ = _ast(tmp_path, bad)
    assert [x.rule for x in v] == ["WC003"]


def test_wc003_blanket_except_does_not_count(tmp_path):
    src = (
        "from dlrover_tpu.common.serde import deserialize\n"
        "def f(b):\n"
        "    try:\n"
        "        return deserialize(b)\n"
        "    except Exception:\n"  # the abort path, not a skew degrade
        "        return None\n"
    )
    v, _ = _ast(tmp_path, src)
    assert [x.rule for x in v] == ["WC003"]


def test_wc003_typed_handler_counts(tmp_path):
    src = (
        "from dlrover_tpu.common.serde import (\n"
        "    UnknownMessageError, deserialize)\n"
        "def f(b):\n"
        "    try:\n"
        "        return deserialize(b)\n"
        "    except (ValueError, UnknownMessageError):\n"
        "        return None\n"
        "def g(b):\n"
        "    try:\n"
        "        return deserialize(b)\n"
        "    except UnknownMessageError as e:\n"
        "        raise RuntimeError(e)\n"
    )
    v, _ = _ast(tmp_path, src)
    assert not v


def test_wc004_int_dict_key_hint(tmp_path):
    bad = (
        "from typing import Dict\n"
        "from dlrover_tpu.common.serde import message\n"
        "@message\n"
        "class Evil:\n"
        "    by_rank: Dict[int, str] = None\n"
        "@message\n"
        "class Fine:\n"
        "    by_name: Dict[str, int] = None\n"
        "    untyped: Dict = None\n"
    )
    v, _ = _ast(tmp_path, bad)
    assert [x.rule for x in v] == ["WC004"]
    assert "Evil.by_rank" in v[0].message


# ---------------------------------------------------------------------------
# seeded regressions (the CI gate proof)
# ---------------------------------------------------------------------------


def test_seeded_unrecorded_message_fails_wirecheck_and_cli():
    """Acceptance: registering a wire message without recording it in
    the schema (here: with a default-less field, the WC001 class) makes
    `python -m dlrover_tpu.lint --wire` exit nonzero."""
    from dlrover_tpu.lint.__main__ import main

    @serde.message
    class SeededSkewRegression:
        required_field: int  # no default — the N-1 decode breaker

    try:
        res = wirecheck.run()
        assert res.failed
        assert any(
            "SeededSkewRegression added" in d for d in res.schema_drift
        )
        assert any(
            "SeededSkewRegression" in c for c in res.corpus_failures
        )
        assert main(["--wire"]) == 1
    finally:
        del serde._REGISTRY["SeededSkewRegression"]


def test_wire_cli_clean_tree_exits_zero():
    from dlrover_tpu.lint.__main__ import main

    assert main(["--wire"]) == 0


def test_wire_cli_usage_errors():
    from dlrover_tpu.lint.__main__ import main

    assert main(["--wire", "--race"]) == 2
    assert main(["--fix-wire-schema"]) == 2


# ---------------------------------------------------------------------------
# serde hardening + the typed client/server paths
# ---------------------------------------------------------------------------


def test_unknown_message_error_is_typed_and_valueerror_compatible():
    with pytest.raises(UnknownMessageError) as ei:
        serde.deserialize(b'{"_t":"MessageFromTheFuture"}')
    assert ei.value.type_name == "MessageFromTheFuture"
    assert isinstance(ei.value, ValueError)  # old handlers keep working


def test_non_string_dict_keys_banned_at_encode():
    with pytest.raises(TypeError, match="non-string dict key"):
        serde.serialize(msg.GlobalStepReport(comm_links={1: 2}))
    # string keys round-trip with the key TYPE preserved
    rep = msg.GlobalStepReport(comm_links={"ici": 5, "dcn": 7})
    back = serde.deserialize(serde.serialize(rep))
    assert back.comm_links == {"ici": 5, "dcn": 7}


def test_rpc_client_maps_unknown_type_into_classification():
    """The OverloadedResponse hazard class, closed: a response type
    this binary cannot decode surfaces as the typed, non-retryable
    UnknownMessageTypeError naming the _t — never a raw ValueError
    escaping the retry loop."""
    from dlrover_tpu.rpc.transport import RpcClient

    client = RpcClient("localhost:1")  # lazy channel: never dialed
    client._get = lambda payload, timeout=None, metadata=None: (
        b'{"_t":"FutureShedSignal","pressure":9}'
    )
    with pytest.raises(rpc_policy.UnknownMessageTypeError) as ei:
        client.get(msg.NumNodesWaitingRequest(), retries=3)
    assert "FutureShedSignal" in str(ei.value)
    assert rpc_policy.classify(ei.value) == rpc_policy.APPLICATION
    client.close()


def test_rpc_server_degrades_unknown_request_to_simple_response():
    from dlrover_tpu.rpc.transport import RpcServer

    class NullServicer:
        def get(self, m, ctx):
            return msg.SimpleResponse()

        def report(self, m, ctx):
            return msg.SimpleResponse()

    class Ctx:
        def invocation_metadata(self):
            return ()

        def abort(self, code, details):  # pragma: no cover
            raise AssertionError(f"aborted: {code} {details}")

    server = RpcServer(NullServicer(), port=0)
    try:
        wire = server._handle_get(b'{"_t":"LeaseRequestV9"}', Ctx())
        resp = serde.deserialize(wire)
        assert isinstance(resp, msg.SimpleResponse)
        assert not resp.success
        assert "LeaseRequestV9" in resp.reason
        assert "version skew" in resp.reason
        wire = server._handle_report(b'{"_t":"Telemetry2"}', Ctx())
        resp = serde.deserialize(wire)
        assert not resp.success and "Telemetry2" in resp.reason
    finally:
        server.stop(0)


def test_loopback_counts_decode_errors_and_raises_typed():
    from dlrover_tpu.fleet.loopback import (
        LoopbackClient, MasterEndpoint, RpcStats,
    )

    class GhostShim:
        def request_wire(self, payload):
            return payload, None

        def response_wire(self, payload):
            return b'{"_t":"GhostResponse"}'

    class Echo:
        def get(self, m, ctx):
            return msg.SimpleResponse()

        def report(self, m, ctx):
            return msg.SimpleResponse()

    ep = MasterEndpoint()
    ep.set_master(Echo())
    stats = RpcStats()
    client = LoopbackClient(ep, stats=stats, shim=GhostShim())
    with pytest.raises(rpc_policy.UnknownMessageTypeError):
        client.get(msg.NumNodesWaitingRequest(), retries=1)
    assert stats.snapshot()["decode_errors"] == 1


# ---------------------------------------------------------------------------
# versioned_format + durable migrations
# ---------------------------------------------------------------------------


def test_versioned_format_wrap_parse_and_crossed_format():
    fmt = versioned_format.VersionedFormat("t_fmt", 3)
    doc = fmt.wrap({"a": 1})
    assert doc == {"_format": "t_fmt", "_v": 3, "a": 1}
    assert fmt.parse(doc) == {"a": 1}
    with pytest.raises(versioned_format.FormatError):
        fmt.parse({"_format": "other", "_v": 3})
    # re-wrapping an already-enveloped doc would stamp a STALE version
    # (dict-merge lets later keys win) — rejected loudly instead
    with pytest.raises(ValueError, match="reserved envelope key"):
        fmt.wrap(doc)
    with pytest.raises(ValueError, match="reserved envelope key"):
        fmt.wrap({"_v": 1, "a": 1})


def test_ast_registry_crosscheck_catches_unimported_vocabulary(tmp_path):
    """The brain/messages.py failure mode, machine-checked: an
    @message class in the scanned source whose module the runtime
    registry imports do not reach fails the gate instead of being
    silently excluded from every wirecheck layer."""
    (tmp_path / "orphan_messages.py").write_text(
        "from dlrover_tpu.common.serde import message\n"
        "@message\n"
        "class OrphanVocabulary:\n"
        "    x: int = 0\n"
    )
    found = wirecheck.ast_message_classes([str(tmp_path)])
    assert "OrphanVocabulary" in found
    res = wirecheck.run(paths=[str(tmp_path)])
    assert any(
        "OrphanVocabulary" in d and "NOT in the runtime registry" in d
        for d in res.schema_drift
    )


def test_versioned_format_legacy_migration_and_newer():
    fmt = versioned_format.VersionedFormat("t_fmt2", 3)
    # version-less -> legacy adapter
    out = fmt.parse({"a": 1}, legacy=lambda p: {**p, "adapted": True})
    assert out == {"a": 1, "adapted": True}
    # older version -> registered migration
    out = fmt.parse(
        {"_v": 2, "a": 1},
        migrations={2: lambda p: {**p, "migrated": True}},
    )
    assert out["migrated"]
    # NEWER version -> best-effort passthrough (master rollback)
    out = fmt.parse({"_format": "t_fmt2", "_v": 9, "a": 1, "future": 2})
    assert out == {"a": 1, "future": 2}


def test_register_rejects_conflicting_version():
    versioned_format.register("t_conflict", 2)
    assert versioned_format.register("t_conflict", 2).version == 2
    with pytest.raises(ValueError):
        versioned_format.register("t_conflict", 3)
    del versioned_format.FORMATS["t_conflict"]


def test_shard_ckpt_v2_stamped_and_legacy_5_element_decode():
    from dlrover_tpu.master.shard.dataset_manager import (
        DatasetShardCheckpoint,
    )

    ckpt = DatasetShardCheckpoint(
        dataset_name="d", todo=[[100, 200]], doing=[[0, 100]],
        epoch=1, completed_records=7,
        doing_meta=[[4, 2, "", 0, 100, 9]], task_id_seq=5,
        leases=[[2, 9, 50.0, [4], 40.0]], lease_seq=9,
    )
    doc = json.loads(ckpt.to_json())
    assert doc["_format"] == "dataset_shard_ckpt" and doc["_v"] == 2
    back = DatasetShardCheckpoint.from_json(ckpt.to_json())
    assert back == ckpt
    # the pre-versioning writer: no envelope, 5-element doing_meta
    legacy = {
        "dataset_name": "d", "todo": [[100, 200]], "doing": [[0, 100]],
        "epoch": 1, "completed_records": 7,
        "doing_meta": [[4, 2, "", 0, 100]], "task_id_seq": 5,
    }
    back = DatasetShardCheckpoint.from_json(json.dumps(legacy))
    assert back.doing_meta == [[4, 2, "", 0, 100, -1]]
    assert back.epoch == 1 and back.leases == []


def test_state_store_docs_versioned_and_legacy_readable(tmp_path):
    from dlrover_tpu.master.state_store import (
        FileStateBackend, MasterStateManager, SPEED_FORMAT,
    )

    backend = FileStateBackend(str(tmp_path))
    mgr = MasterStateManager(backend, job_uid="u1")
    mgr.save_speed({"global_step": 11, "total_downtime": 2.0})
    raw = json.loads(backend.get(MasterStateManager.K_SPEED))
    assert raw["_format"] == "state_speed"
    assert raw["_v"] == SPEED_FORMAT.version
    loaded = mgr.load_speed()
    assert loaded["global_step"] == 11
    assert "_format" not in loaded and "_v" not in loaded
    # a PRE-versioning master's document (no envelope) still loads
    backend.set(
        MasterStateManager.K_SPEED,
        json.dumps({"global_step": 5, "job_uid": "u1"}),
    )
    assert mgr.load_speed()["global_step"] == 5
    # and the job_uid fence still applies on top of the envelope
    backend.set(
        MasterStateManager.K_SPEED,
        json.dumps(SPEED_FORMAT.wrap(
            {"global_step": 9, "job_uid": "OTHER"}
        )),
    )
    assert mgr.load_speed() is None


def test_state_store_planner_and_dataset_docs_versioned(tmp_path):
    from dlrover_tpu.master.state_store import (
        FileStateBackend, MasterStateManager,
    )

    backend = FileStateBackend(str(tmp_path))
    mgr = MasterStateManager(backend, job_uid="u1")
    mgr.save_planner({"ledger": [1, 2]})
    assert mgr.load_planner() == {"ledger": [1, 2]}
    mgr.save_dataset("ds", {"dataset_size": 10}, json.dumps({"todo": []}))
    docs = mgr.load_datasets()
    assert docs["ds"]["params"] == {"dataset_size": 10}
    assert "_format" not in docs["ds"]


# ---------------------------------------------------------------------------
# skew shim units
# ---------------------------------------------------------------------------


def test_shim_strips_fields_recursively_and_counts():
    shim = SkewShim({"NodeMeta": ["slice_name"]})
    resp = msg.RunningNodesResponse(
        nodes=[msg.NodeMeta(node_id=1, slice_name="s0"),
               msg.NodeMeta(node_id=2, slice_name="s1")]
    )
    wire = shim.response_wire(serde.serialize(resp))
    back = serde.deserialize(wire)
    # nested messages stripped too; the local default fills in
    assert [n.slice_name for n in back.nodes] == ["", ""]
    assert shim.stripped_fields == 2


def test_shim_unknown_reply_matches_transport_skew_reply():
    from dlrover_tpu.rpc.transport import _skew_reply

    shim = SkewShim(unknown_types=["ShardLeaseRequest"])
    payload = serde.serialize(msg.ShardLeaseRequest(dataset_name="d"))
    _, override = shim.request_wire(payload)
    assert override is not None
    assert serde.deserialize(override) == _skew_reply(
        UnknownMessageError("ShardLeaseRequest")
    )
    assert shim.unknown_replies == 1
    # known types pass through untouched (no drop rules)
    stripped, override = shim.request_wire(
        serde.serialize(msg.TaskRequest(dataset_name="d"))
    )
    assert override is None
    assert serde.deserialize(stripped) == msg.TaskRequest(
        dataset_name="d"
    )
