"""wirecheck: wire & durable-format schema verification (the fourth
machine-checked invariant layer — docs/design/wirecheck.md).

graftlint checks the AST, shardcheck the lowered IR, racecheck the lock
discipline; wirecheck checks the PROTOCOL. The control plane speaks ~60
serde dataclasses (common/messages.py) and persists five durable JSON
families (state-store speed/planner/nodes/dataset documents and the
``DatasetShardCheckpoint``), and a production fleet rolls upgrades: at
any moment an N-1 agent talks to an N master (or the inverse), and a
relaunched master reads durable state an older binary wrote. Version
skew safety used to be convention — scattered "skew-safe" comments and
per-site ``getattr`` fallbacks, with one documented-but-unfixed hazard
(the OverloadedResponse AttributeError class). wirecheck makes it a
checked-in contract, three ways:

1. **Schema registry** (``lint/wire_schema.json``): field names, type
   hints and default-presence of every registered message, plus the
   version of every registered durable format, extracted from the live
   registries and two-sided-diffed like ``lock_order.json`` — ANY
   drift (field added/removed, type changed, default dropped, format
   version bumped) fails until ``--fix-wire-schema`` records it as a
   reviewable one-line diff with a compat note (``--wire-note``).
   Fields recorded as added to an EXISTING message are auto-marked
   ``skew_guarded`` — they postdate the baseline, so WC002 requires
   their reads to tolerate absence.

2. **Skew rules** over the AST (graftlint suppression syntax applies):

   - WC001 default-less wire field: an N-1 peer's message lacks the
     new field, and ``cls(**kwargs)`` with no default raises TypeError
     at DECODE time — the worst place, inside the transport.
   - WC002 unguarded skew-field read: a consumer reading a
     ``skew_guarded`` field via plain attribute access. Under skew the
     object at that site can be the typed fallback (``SimpleResponse``
     from an old master that did not know the request) — the newest
     fields meet the oldest masters, so their reads must be
     absence-tolerant (``getattr`` with a default), which is exactly
     the convention every shipped skew-safe field already follows.
   - WC003 unknown-message hard-fail: every ``deserialize`` call site
     outside serde must lexically handle
     :class:`~dlrover_tpu.common.serde.UnknownMessageError` — servers
     degrade to ``SimpleResponse``, clients raise the typed classification
     error — so an unknown ``_t`` can never escape as a raw
     ValueError (the OverloadedResponse bug class). A blanket
     ``except Exception`` deliberately does NOT count: that is the
     abort-INTERNAL path, not a skew degrade.
   - WC004 non-string dict keys in a message hint: serde's JSON wire
     round-trips dict keys as strings, so ``Dict[int, ...]`` silently
     changes key type across one hop (now also banned at runtime by
     ``serde._encode``).

3. **Golden corpus** (``lint/wire_corpus/``): serialized bytes of every
   registered message (instances synthesized from type hints) and
   every durable format — including FROZEN legacy variants (the
   version-less 5-element ``doing_meta`` checkpoint) — replayed on
   every run: current code must decode every checked-in byte stream
   and reproduce every recorded field value. Adding a field with a
   default keeps the old corpus decodable (that IS the N-1 test); a
   breaking change fails replay and forces an explicit, reviewable
   ``--fix-wire-corpus`` regeneration. Known limit: the gate replays
   the corpus checked in at the PR's head, so a regeneration in the
   same PR as the breaking change passes mechanically — the defense is
   that the regeneration is a diff a reviewer sees, next to the schema
   history entry that must accompany it.

The runtime companion is :mod:`dlrover_tpu.lint.skew_shim` + the fleet
harness ``version_skew`` scenarios: a serde-level shim makes the
in-process wire behave like an N-1 peer (fields dropped, unknown types
answered the old way), gated on exactly-once convergence and zero raw
decode errors in both skew directions.

Stdlib-only (ast + json + dataclasses + typing): runs in the dep-free
CI lint job alongside graftlint and racecheck.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import typing
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from dlrover_tpu.lint import engine
from dlrover_tpu.lint.engine import SourceFile, Violation

DEFAULT_SCHEMA = os.path.join(os.path.dirname(__file__), "wire_schema.json")
DEFAULT_CORPUS_DIR = os.path.join(os.path.dirname(__file__), "wire_corpus")
#: the package root the AST rules scan by default
DEFAULT_PATHS = (os.path.dirname(os.path.dirname(__file__)),)

WC_RULES = [
    ("WC001", "defaultless-wire-field",
     "wire-message field without a default: an N-1 peer's message "
     "lacking it TypeErrors cls(**kwargs) at decode"),
    ("WC002", "unguarded-skew-field-read",
     "plain read of a skew_guarded (post-baseline) message field: must "
     "tolerate absence via getattr — under skew the object can be the "
     "typed SimpleResponse fallback"),
    ("WC003", "unknown-message-hard-fail",
     "deserialize call site without UnknownMessageError handling: an "
     "unknown _t must degrade (SimpleResponse / typed classification error), "
     "never escape as a raw ValueError"),
    ("WC004", "non-string-dict-keys",
     "Dict[non-str, ...] in a wire-message hint: JSON round-trips keys "
     "as strings, silently changing the key type on the peer"),
    ("WC005", "schema-drift",
     "wire/durable schema differs from the checked-in "
     "wire_schema.json: record the change with --fix-wire-schema"),
    ("WC006", "corpus-replay",
     "golden corpus replay failure: current code cannot decode (or no "
     "longer reproduces) checked-in serialized bytes"),
]

#: receiver names that conventionally hold a decoded wire object; WC002
#: matches only these bases, trading recall for precision (a plain read
#: through any other name is invisible to the rule — documented limit)
WIRE_BASES = frozenset(
    {"resp", "response", "request", "req", "reply", "grant", "ack"}
)

#: durable formats whose payload is itself a dataclass — field lists
#: are extracted into the schema like message fields
_DURABLE_DATACLASSES = {
    "dataset_shard_ckpt": (
        "dlrover_tpu.master.shard.dataset_manager",
        "DatasetShardCheckpoint",
    ),
}


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------


def message_registry() -> Dict[str, type]:
    """Every wire-serializable class, by importing BOTH vocabulary
    modules (the ``@message`` decorator registers on import). Keep this
    list in sync with every module that defines ``@message`` classes —
    a vocabulary module missing here would make the schema gate
    import-order-dependent (and under-scoped)."""
    import dlrover_tpu.brain.messages  # noqa: F401  (registration)
    import dlrover_tpu.common.messages  # noqa: F401  (registration)
    from dlrover_tpu.common import serde

    return dict(serde._REGISTRY)


def durable_formats():
    """Every registered durable format, by importing the writers."""
    import dlrover_tpu.master.shard.dataset_manager  # noqa: F401
    import dlrover_tpu.master.state_store  # noqa: F401
    from dlrover_tpu.common import versioned_format

    return dict(versioned_format.FORMATS)


def _durable_dataclass(name: str):
    spec = _DURABLE_DATACLASSES.get(name)
    if spec is None:
        return None
    import importlib

    return getattr(importlib.import_module(spec[0]), spec[1])


# ---------------------------------------------------------------------------
# schema extraction + two-sided diff
# ---------------------------------------------------------------------------


def _type_str(hint: Any) -> str:
    """Stable, human-auditable rendering of a type hint."""
    if hint is None:
        return "Any"
    if hint is type(None):  # noqa: E721
        return "None"
    origin = typing.get_origin(hint)
    if origin is None:
        return getattr(hint, "__name__", str(hint))
    args = typing.get_args(hint)
    if origin is typing.Union:
        non_none = [a for a in args if a is not type(None)]  # noqa: E721
        if len(args) == len(non_none) + 1 and len(non_none) == 1:
            return f"Optional[{_type_str(non_none[0])}]"
        return "Union[" + ", ".join(_type_str(a) for a in args) + "]"
    base = {list: "List", dict: "Dict", tuple: "Tuple", set: "Set"}.get(
        origin, getattr(origin, "__name__", str(origin))
    )
    if not args:
        return base
    return base + "[" + ", ".join(_type_str(a) for a in args) + "]"


_MISSING = dataclasses.MISSING


def extract_schema() -> Dict:
    """The live registries rendered as the schema document's structural
    half (metadata like ``skew_guarded``/``note`` lives only in the
    checked-in file and is merged on ``--fix``)."""
    messages: Dict[str, Dict] = {}
    for name, cls in sorted(message_registry().items()):
        hints = typing.get_type_hints(cls)
        fields: Dict[str, Dict] = {}
        for f in dataclasses.fields(cls):
            fields[f.name] = {
                "type": _type_str(hints.get(f.name)),
                "default": (
                    f.default is not _MISSING
                    or f.default_factory is not _MISSING
                ),
            }
        messages[name] = {"fields": fields}
    durable: Dict[str, Dict] = {}
    for name, fmt in sorted(durable_formats().items()):
        entry: Dict[str, Any] = {"version": fmt.version}
        cls = _durable_dataclass(name)
        if cls is not None:
            entry["fields"] = sorted(
                f.name for f in dataclasses.fields(cls)
            )
        durable[name] = entry
    return {"messages": messages, "durable": durable}


def load_schema(path: str = DEFAULT_SCHEMA) -> Optional[Dict]:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        return None


def diff_schema(current: Dict, baseline: Dict) -> List[str]:
    """Two-sided structural diff, one human line per drift. Empty =
    clean. BOTH directions fail: an unrecorded addition and a stale
    baseline entry are equally drift."""
    out: List[str] = []
    cur_msgs = current.get("messages", {})
    base_msgs = baseline.get("messages", {})
    for name in sorted(set(cur_msgs) - set(base_msgs)):
        out.append(f"message {name} added (not in wire_schema.json)")
    for name in sorted(set(base_msgs) - set(cur_msgs)):
        out.append(
            f"message {name} removed (still in wire_schema.json) — "
            "removal breaks every peer still sending it"
        )
    for name in sorted(set(cur_msgs) & set(base_msgs)):
        cf = cur_msgs[name].get("fields", {})
        bf = base_msgs[name].get("fields", {})
        for fname in sorted(set(cf) - set(bf)):
            kind = (
                "WITHOUT a default (breaks N-1 decode)"
                if not cf[fname]["default"]
                else "with a default (safe add — still record it)"
            )
            out.append(f"field {name}.{fname} added {kind}")
        for fname in sorted(set(bf) - set(cf)):
            out.append(
                f"field {name}.{fname} removed — peers still sending it "
                "are fine (serde drops unknowns) but every consumer "
                "reading it breaks; record with a compat note"
            )
        for fname in sorted(set(cf) & set(bf)):
            if cf[fname]["type"] != bf[fname]["type"]:
                out.append(
                    f"field {name}.{fname} type changed "
                    f"{bf[fname]['type']} -> {cf[fname]['type']}"
                )
            if bf[fname]["default"] and not cf[fname]["default"]:
                out.append(
                    f"field {name}.{fname} LOST its default — an N-1 "
                    "peer's message lacking it now TypeErrors at decode"
                )
    cur_dur = current.get("durable", {})
    base_dur = baseline.get("durable", {})
    for name in sorted(set(cur_dur) - set(base_dur)):
        out.append(f"durable format {name} added")
    for name in sorted(set(base_dur) - set(cur_dur)):
        out.append(f"durable format {name} removed")
    for name in sorted(set(cur_dur) & set(base_dur)):
        cv, bv = cur_dur[name].get("version"), base_dur[name].get("version")
        if cv != bv:
            out.append(
                f"durable format {name} version changed {bv} -> {cv} — "
                "regenerate its corpus entry and keep the legacy pin"
            )
        cfields = cur_dur[name].get("fields")
        bfields = base_dur[name].get("fields")
        if cfields is not None and bfields is not None and cfields != bfields:
            added = sorted(set(cfields) - set(bfields))
            removed = sorted(set(bfields) - set(cfields))
            out.append(
                f"durable format {name} fields changed "
                f"(+{added or '[]'} -{removed or '[]'})"
            )
    return out


def write_schema(
    path: str, current: Dict, old: Optional[Dict], note: str = ""
) -> Dict:
    """Record the current extraction, preserving per-field metadata
    from the old file and auto-marking fields newly added to EXISTING
    messages as ``skew_guarded`` (they postdate the baseline — WC002
    will require absence-tolerant reads). Appends a history entry with
    the diff and the operator's compat note."""
    old = old or {"messages": {}, "durable": {}, "revision": 0,
                  "history": []}
    changes = diff_schema(current, old)
    merged = json.loads(json.dumps(current))  # deep copy
    old_msgs = old.get("messages", {})
    for name, m in merged["messages"].items():
        bf = old_msgs.get(name, {}).get("fields", {})
        existed = name in old_msgs
        for fname, f in m["fields"].items():
            if fname in bf:
                for meta in ("skew_guarded", "note"):
                    if meta in bf[fname]:
                        f[meta] = bf[fname][meta]
            elif existed:
                f["skew_guarded"] = True
    revision = int(old.get("revision", 0)) + (1 if changes else 0)
    data = {
        "comment": (
            "wirecheck wire & durable-format schema registry "
            "(docs/design/wirecheck.md). Two-sided-diffed by CI: any "
            "drift fails until recorded with: python -m dlrover_tpu."
            "lint --wire --fix-wire-schema --wire-note '<why this is "
            "compatible>'. skew_guarded marks fields added after a "
            "message first shipped — WC002 requires their reads to "
            "tolerate absence."
        ),
        "revision": revision,
        "history": list(old.get("history", [])),
        "messages": merged["messages"],
        "durable": merged["durable"],
    }
    if changes:
        data["history"].append({
            "revision": revision,
            "note": note or "(no compat note given)",
            "changes": changes,
        })
    tmp = f"{path}.tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return data


def guarded_field_names(schema: Dict) -> Set[str]:
    """Field names WC002 enforces: marked ``skew_guarded`` in EVERY
    message that has a field of that name. A name that is guarded in
    one message and baseline in another (e.g. ``digest``: post-baseline
    on GlobalStepReport, born-with on WorkerReport) is ambiguous to a
    name-based AST rule and is skipped — a documented precision/recall
    trade."""
    seen: Dict[str, List[bool]] = {}
    for m in schema.get("messages", {}).values():
        for fname, f in m.get("fields", {}).items():
            seen.setdefault(fname, []).append(
                bool(f.get("skew_guarded", False))
            )
    return {n for n, flags in seen.items() if all(flags)}


def skew_baseline_drops(schema: Optional[Dict] = None) -> Dict[str, List[str]]:
    """message -> skew_guarded fields: the machine-readable
    approximation of "what an N-1 peer does not know", used by the
    fleet harness's version_skew shim as its default drop set."""
    schema = schema or load_schema() or {}
    out: Dict[str, List[str]] = {}
    for name, m in schema.get("messages", {}).items():
        fields = sorted(
            f for f, meta in m.get("fields", {}).items()
            if meta.get("skew_guarded")
        )
        if fields:
            out[name] = fields
    return out


# ---------------------------------------------------------------------------
# golden corpus: synthesis, write, replay
# ---------------------------------------------------------------------------


def synth_value(hint: Any, salt: str, registry: Dict[str, type],
                depth: int = 0) -> Any:
    """A deterministic representative value for a type hint. Depth-
    bounded so a (hypothetical) recursive message terminates."""
    if depth > 4:
        return None
    origin = typing.get_origin(hint)
    if hint is None or hint is Any:
        return f"any-{salt}"
    if origin is typing.Union:
        non_none = [a for a in typing.get_args(hint)
                    if a is not type(None)]  # noqa: E721
        return synth_value(non_none[0], salt, registry, depth) \
            if non_none else None
    if origin in (list, tuple, set) or hint in (list, tuple, set):
        args = typing.get_args(hint)
        if origin is tuple or hint is tuple:
            if args and args[-1] is not Ellipsis:
                return tuple(
                    synth_value(a, f"{salt}.{i}", registry, depth + 1)
                    for i, a in enumerate(args)
                )
            return (1, 2)
        elem = (
            synth_value(args[0], f"{salt}.0", registry, depth + 1)
            if args else f"item-{salt}"
        )
        return [elem]
    if origin is dict or hint is dict:
        args = typing.get_args(hint)
        val = (
            synth_value(args[1], f"{salt}.v", registry, depth + 1)
            if len(args) == 2 else f"val-{salt}"
        )
        return {f"k-{salt}": val}
    if hint is str:
        return f"s-{salt}"
    if hint is bool:
        return True
    if hint is int:
        return 7
    if hint is float:
        return 1.5
    if hint is bytes:
        return b"\x00\x01\xfe"
    if isinstance(hint, type) and dataclasses.is_dataclass(hint):
        return synth_instance(hint, registry, depth + 1)
    return f"opaque-{salt}"


def synth_instance(cls: type, registry: Dict[str, type],
                   depth: int = 0) -> Any:
    hints = typing.get_type_hints(cls)
    kwargs = {}
    for f in dataclasses.fields(cls):
        kwargs[f.name] = synth_value(
            hints.get(f.name), f"{cls.__name__}.{f.name}", registry, depth
        )
    return cls(**kwargs)


#: frozen durable-format pins. "current" entries regenerate with
#: --fix-wire-corpus; ".legacy" entries are FROZEN artifacts of the
#: pre-versioning writers (never regenerated from live code — they pin
#: that old bytes stay decodable forever).
_STATE_PAYLOADS: Dict[str, Dict] = {
    "state_speed": {"job_uid": "corpus", "global_step": 42,
                    "total_downtime": 3.5},
    "state_nodes": {"job_uid": "corpus",
                    "nodes": {"0": {"status": "RUNNING"}}},
    "state_planner": {"job_uid": "corpus",
                      "planner": {"ledger": [], "cooldown_until": 0.0}},
    "state_dataset": {"job_uid": "corpus",
                      "params": {"dataset_name": "d", "dataset_size": 200},
                      "ckpt": {"dataset_name": "d", "todo": [[0, 200]]},
                      "time": 1.0},
}

_LEGACY_DURABLE: Dict[str, Dict] = {
    # the pre-versioning shard checkpoint: no _format/_v, and the
    # doing_meta entry carries only 5 elements (pre-lease writer) — the
    # decode must fill the fence with -1 (legacy per-task dispatch)
    "dataset_shard_ckpt": {
        "dataset_name": "corpus",
        "todo": [[100, 200]],
        "doing": [[0, 100]],
        "epoch": 1,
        "completed_records": 300,
        "partition_offsets": {},
        "doing_meta": [[7, 3, "", 0, 100]],
        "task_id_seq": 8,
    },
    "state_speed": {"job_uid": "corpus", "global_step": 42,
                    "total_downtime": 3.5},
    "state_nodes": {"job_uid": "corpus",
                    "nodes": {"0": {"status": "RUNNING"}}},
    "state_planner": {"job_uid": "corpus",
                      "planner": {"ledger": [], "cooldown_until": 0.0}},
    "state_dataset": {"job_uid": "corpus",
                      "params": {"dataset_name": "d", "dataset_size": 200},
                      "ckpt": {"dataset_name": "d", "todo": [[0, 200]]},
                      "time": 1.0},
}


def _current_shard_ckpt():
    cls = _durable_dataclass("dataset_shard_ckpt")
    return cls(
        dataset_name="corpus",
        todo=[[100, 200], [200, 300]],
        doing=[[0, 100]],
        epoch=1,
        completed_records=300,
        partition_offsets={"p0": 300},
        doing_meta=[[7, 3, "", 0, 100, 5]],
        task_id_seq=8,
        epoch_unit="pass",
        epoch_factor=1,
        leases=[[3, 5, 1234.5, [7], 1200.0]],
        lease_seq=6,
    )


def _durable_current_doc(name: str) -> Dict:
    if name == "dataset_shard_ckpt":
        return json.loads(_current_shard_ckpt().to_json())
    fmt = durable_formats()[name]
    return fmt.wrap(dict(_STATE_PAYLOADS[name]))


def write_corpus(corpus_dir: str = DEFAULT_CORPUS_DIR) -> List[str]:
    """(Re)generate the golden corpus: one ``msg.<Name>.json`` per
    registered message, one ``durable.<fmt>.json`` per durable format,
    and — written only if absent — the frozen ``durable.<fmt>.legacy
    .json`` pins. Removes corpus files for messages that no longer
    exist (their removal is separately gated by the schema diff).
    Returns the written file names."""
    from dlrover_tpu.common import serde

    os.makedirs(corpus_dir, exist_ok=True)
    registry = message_registry()
    written: List[str] = []
    wanted: Set[str] = set()
    for name, cls in sorted(registry.items()):
        data = json.loads(serde.serialize(synth_instance(cls, registry)))
        fn = f"msg.{name}.json"
        wanted.add(fn)
        _write_json(os.path.join(corpus_dir, fn), data)
        written.append(fn)
    for name in sorted(durable_formats()):
        fn = f"durable.{name}.json"
        wanted.add(fn)
        _write_json(os.path.join(corpus_dir, fn), _durable_current_doc(name))
        written.append(fn)
        legacy = _LEGACY_DURABLE.get(name)
        lfn = f"durable.{name}.legacy.json"
        if legacy is not None:
            wanted.add(lfn)
            lpath = os.path.join(corpus_dir, lfn)
            if not os.path.exists(lpath):  # frozen: write-once
                _write_json(lpath, legacy)
                written.append(lfn)
    for fn in os.listdir(corpus_dir):
        if fn.endswith(".json") and fn not in wanted and not \
                fn.endswith(".legacy.json"):
            os.remove(os.path.join(corpus_dir, fn))
    return written


def _write_json(path: str, data: Dict):
    tmp = f"{path}.tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def check_corpus(corpus_dir: str = DEFAULT_CORPUS_DIR) -> List[str]:
    """Replay every corpus file through current code. One human line
    per failure; empty = clean. The decode side IS the N-1 gate: every
    checked-in byte stream is a message some shipped version wrote."""
    from dlrover_tpu.common import serde

    out: List[str] = []
    if not os.path.isdir(corpus_dir):
        return [f"corpus directory {corpus_dir} missing — run "
                "--fix-wire-corpus"]
    files = sorted(
        fn for fn in os.listdir(corpus_dir) if fn.endswith(".json")
    )
    registry = message_registry()
    formats = durable_formats()
    have_msgs = {
        fn[len("msg."):-len(".json")] for fn in files
        if fn.startswith("msg.")
    }
    for name in sorted(set(registry) - have_msgs):
        out.append(
            f"message {name} has no corpus file — run --fix-wire-corpus"
        )
    for fn in files:
        path = os.path.join(corpus_dir, fn)
        try:
            with open(path, encoding="utf-8") as f:
                data = json.load(f)
        except (OSError, ValueError) as e:
            out.append(f"{fn}: unreadable: {e}")
            continue
        if fn.startswith("msg."):
            out.extend(_replay_message(fn, data, registry, serde))
        elif fn.startswith("durable."):
            out.extend(_replay_durable(fn, data, formats))
    return out


def _replay_message(fn: str, data: Dict, registry, serde) -> List[str]:
    name = fn[len("msg."):-len(".json")]
    if name not in registry:
        return [
            f"{fn}: message {name} no longer registered — old peers "
            "still send it; record the removal in the schema and "
            "regenerate the corpus"
        ]
    try:
        # this IS the corpus gate: any decode failure (Unknown-
        # MessageError included) is caught and REPORTED as a WC006
        # finding — the degrade path is the report itself
        # graftlint: disable=WC003
        obj = serde.deserialize(
            json.dumps(data, separators=(",", ":")).encode()
        )
    except Exception as e:
        return [f"{fn}: DECODE FAILED (an N-1 peer's bytes no longer "
                f"decode): {type(e).__name__}: {e}"]
    if type(obj).__name__ != name:
        return [f"{fn}: decoded as {type(obj).__name__}, expected {name}"]
    try:
        reenc = serde._encode(obj)
    except Exception as e:
        return [f"{fn}: re-encode failed: {type(e).__name__}: {e}"]
    out = []
    for key, val in data.items():
        if key == "_t":
            continue
        if key not in reenc:
            out.append(
                f"{fn}: field {name}.{key} present in corpus but dropped "
                "by decode (field removed?) — consumers of old senders "
                "lose data silently"
            )
        elif reenc[key] != val:
            out.append(
                f"{fn}: field {name}.{key} value drift: corpus {val!r} "
                f"-> decoded-re-encoded {reenc[key]!r}"
            )
    return out


def _replay_durable(fn: str, data: Dict, formats) -> List[str]:
    body = fn[len("durable."):-len(".json")]
    legacy = body.endswith(".legacy")
    name = body[:-len(".legacy")] if legacy else body
    if name not in formats:
        return [f"{fn}: durable format {name} no longer registered"]
    if name == "dataset_shard_ckpt":
        return _replay_shard_ckpt(fn, data, legacy)
    fmt = formats[name]
    if not legacy and int(data.get("_v", -1)) != fmt.version:
        return [
            f"{fn}: corpus stamped v{data.get('_v')} but {name} is "
            f"registered at v{fmt.version} — regenerate the corpus "
            "after recording the version bump"
        ]
    try:
        payload = fmt.parse(data)
    except Exception as e:
        return [f"{fn}: parse failed: {type(e).__name__}: {e}"]
    out = []
    for key, val in data.items():
        if key in ("_format", "_v"):
            continue
        if payload.get(key) != val:
            out.append(
                f"{fn}: durable payload key {key!r} drift: {val!r} -> "
                f"{payload.get(key)!r}"
            )
    return out


def _replay_shard_ckpt(fn: str, data: Dict, legacy: bool) -> List[str]:
    cls = _durable_dataclass("dataset_shard_ckpt")
    try:
        ckpt = cls.from_json(json.dumps(data))
    except Exception as e:
        return [f"{fn}: from_json failed: {type(e).__name__}: {e}"]
    out = []
    if not legacy and int(data.get("_v", -1)) != \
            durable_formats()["dataset_shard_ckpt"].version:
        out.append(
            f"{fn}: corpus stamped v{data.get('_v')} but the format is "
            f"v{durable_formats()['dataset_shard_ckpt'].version} — "
            "regenerate after recording the version bump"
        )
    for entry in ckpt.doing_meta:
        if len(entry) != 6:
            out.append(
                f"{fn}: doing_meta entry {entry!r} not normalized to 6 "
                "elements"
            )
    if legacy and ckpt.doing_meta and ckpt.doing_meta[0][5] != -1:
        out.append(
            f"{fn}: legacy 5-element doing_meta decoded fence "
            f"{ckpt.doing_meta[0][5]!r}, expected -1"
        )
    for key in ("dataset_name", "epoch", "completed_records",
                "task_id_seq"):
        if key in data and getattr(ckpt, key) != data[key]:
            out.append(
                f"{fn}: {key} drift: {data[key]!r} -> "
                f"{getattr(ckpt, key)!r}"
            )
    return out


# ---------------------------------------------------------------------------
# AST rules WC001-WC004
# ---------------------------------------------------------------------------


def _is_message_class(node: ast.ClassDef) -> bool:
    for dec in node.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        if isinstance(target, ast.Name) and target.id == "message":
            return True
        if isinstance(target, ast.Attribute) and target.attr == "message":
            return True
    return False


def _wc001_wc004(src: SourceFile) -> List[Violation]:
    out: List[Violation] = []
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.ClassDef) or not \
                _is_message_class(node):
            continue
        for stmt in node.body:
            if not isinstance(stmt, ast.AnnAssign):
                continue
            fname = getattr(stmt.target, "id", "?")
            if stmt.value is None:
                out.append(src.violation(
                    "WC001", stmt,
                    f"wire field {node.name}.{fname} has no default: an "
                    "N-1 peer's message lacks it and cls(**kwargs) "
                    "TypeErrors at decode — give it a default",
                ))
            bad_key = _non_str_dict_key(stmt.annotation)
            if bad_key is not None:
                out.append(src.violation(
                    "WC004", stmt,
                    f"wire field {node.name}.{fname} is Dict[{bad_key}, "
                    "...]: JSON round-trips keys as str, silently "
                    "changing the key type on the peer — stringify "
                    "explicitly (serde._encode now rejects non-str "
                    "keys at runtime)",
                ))
    return out


def _non_str_dict_key(annotation: ast.AST) -> Optional[str]:
    for node in ast.walk(annotation):
        if not isinstance(node, ast.Subscript):
            continue
        base = node.value
        base_name = (
            base.id if isinstance(base, ast.Name)
            else base.attr if isinstance(base, ast.Attribute) else ""
        )
        if base_name not in ("Dict", "dict", "Mapping"):
            continue
        sl = node.slice
        if isinstance(sl, ast.Tuple) and sl.elts:
            key = sl.elts[0]
            key_name = (
                key.id if isinstance(key, ast.Name)
                else key.attr if isinstance(key, ast.Attribute) else None
            )
            if key_name is not None and key_name != "str":
                return key_name
    return None


def _wc002(src: SourceFile, guarded: Set[str]) -> List[Violation]:
    out: List[Violation] = []
    if not guarded:
        return out
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Attribute):
            continue
        if not isinstance(node.ctx, ast.Load):
            continue
        if node.attr not in guarded:
            continue
        base = node.value
        if not (isinstance(base, ast.Name) and base.id in WIRE_BASES):
            continue
        parent = getattr(node, "_graftlint_parent", None)
        if isinstance(parent, ast.Call) and parent.func is node:
            continue  # method call, not a field read
        out.append(src.violation(
            "WC002", node,
            f"plain read of skew-guarded field .{node.attr}: under "
            "version skew this object can be the typed SimpleResponse "
            "fallback (old master, unknown request type) — use "
            f"getattr({base.id}, \"{node.attr}\", <default>)",
        ))
    return out


def _wc003(src: SourceFile) -> List[Violation]:
    if src.rel_path.endswith("common/serde.py"):
        return []
    out: List[Violation] = []
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = (
            node.func.id if isinstance(node.func, ast.Name)
            else node.func.attr if isinstance(node.func, ast.Attribute)
            else ""
        )
        if fname != "deserialize":
            continue
        if not _unknown_handled(node):
            out.append(src.violation(
                "WC003", node,
                "deserialize call without UnknownMessageError handling "
                "in an enclosing try: an unknown _t (version skew) "
                "must degrade to SimpleResponse (servers) or the typed "
                "classification error (clients), never escape as a raw "
                "ValueError — and a blanket `except Exception` is the "
                "abort path, not a skew degrade",
            ))
    return out


def _unknown_handled(call: ast.Call) -> bool:
    node: ast.AST = call
    parent = getattr(node, "_graftlint_parent", None)
    while parent is not None:
        if isinstance(parent, ast.Try) and node in parent.body:
            for handler in parent.handlers:
                if handler.type is not None and _mentions_unknown(
                        handler.type):
                    return True
        node, parent = parent, getattr(
            parent, "_graftlint_parent", None
        )
    return False


def _mentions_unknown(expr: ast.AST) -> bool:
    for n in ast.walk(expr):
        if isinstance(n, ast.Name) and n.id in (
                "UnknownMessageError", "UnknownMessageTypeError"):
            return True
        if isinstance(n, ast.Attribute) and n.attr in (
                "UnknownMessageError", "UnknownMessageTypeError"):
            return True
    return False


def ast_message_classes(paths: Sequence[str]) -> Dict[str, str]:
    """Every ``@message``-decorated class name found by walking the
    SOURCE under ``paths`` -> its file. Cross-checked against the
    runtime registry in :func:`run`: a vocabulary module that
    :func:`message_registry` does not import would otherwise be
    silently excluded from the schema diff, the corpus, WC002's guard
    set and the skew shim's drop map — exactly how the 11
    brain/messages.py classes were import-order-invisible to this
    gate's first extraction."""
    out: Dict[str, str] = {}
    for full, display in engine.iter_py_files(paths):
        try:
            with open(full, encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=full)
        except (OSError, SyntaxError, ValueError):
            continue  # reported as an error by check_ast's own walk
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef) and _is_message_class(node):
                out[node.name] = display
    return out


def check_ast(
    paths: Sequence[str], schema: Optional[Dict]
) -> Tuple[List[Violation], List[str]]:
    guarded = guarded_field_names(schema or {})
    violations: List[Violation] = []
    errors: List[str] = []
    for full, display in engine.iter_py_files(paths):
        try:
            with open(full, encoding="utf-8") as f:
                text = f.read()
            src = SourceFile(full, text, rel_path=display)
        except (OSError, SyntaxError, ValueError) as e:
            errors.append(f"{display}: unparsable: {e}")
            continue
        found = (
            _wc001_wc004(src) + _wc002(src, guarded) + _wc003(src)
        )
        violations.extend(
            v for v in found if not src.suppressed(v.rule, v.line)
        )
    violations.sort(key=lambda v: (v.path, v.line, v.rule))
    return violations, errors


# ---------------------------------------------------------------------------
# one-call entry (CLI and tests share it)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class WireResult:
    violations: List[Violation]  # AST findings
    schema_drift: List[str]
    corpus_failures: List[str]
    errors: List[str]

    @property
    def failed(self) -> bool:
        return bool(
            self.violations or self.schema_drift
            or self.corpus_failures or self.errors
        )


def run(
    paths: Optional[Sequence[str]] = None,
    schema_path: str = DEFAULT_SCHEMA,
    corpus_dir: str = DEFAULT_CORPUS_DIR,
    fix_schema: bool = False,
    fix_corpus: bool = False,
    note: str = "",
) -> WireResult:
    current = extract_schema()
    baseline = load_schema(schema_path)
    if fix_schema:
        write_schema(schema_path, current, baseline, note=note)
        baseline = load_schema(schema_path)
    if fix_corpus:
        write_corpus(corpus_dir)
    drift: List[str] = []
    if baseline is None:
        drift.append(
            f"no schema at {schema_path} — record one with "
            "--fix-wire-schema"
        )
    else:
        drift = diff_schema(current, baseline)
    # the AST<->registry cross-check: every @message class in the
    # scanned SOURCE must be reachable through message_registry()'s
    # imports, or the whole gate is silently under-scoped for it
    registered = set(message_registry())
    for name, where in sorted(ast_message_classes(
            paths or DEFAULT_PATHS).items()):
        if name not in registered:
            drift.append(
                f"message {name} ({where}) is @message-decorated but "
                "NOT in the runtime registry — its module is missing "
                "from wirecheck.message_registry()'s vocabulary "
                "imports, so the schema/corpus/skew gates cannot see it"
            )
    corpus = check_corpus(corpus_dir)
    violations, errors = check_ast(
        paths or DEFAULT_PATHS, baseline or current
    )
    return WireResult(violations, drift, corpus, errors)


def report(result: WireResult, out=None) -> None:
    import sys

    out = out or sys.stdout
    for v in result.violations:
        print(v.format(), file=out)
    for line in result.schema_drift:
        print(f"WC005 schema drift: {line}", file=out)
    for line in result.corpus_failures:
        print(f"WC006 corpus: {line}", file=out)
    for e in result.errors:
        print(f"ERROR {e}", file=out)
    print(
        f"wirecheck: {len(result.violations)} AST violation(s), "
        f"{len(result.schema_drift)} schema drift(s), "
        f"{len(result.corpus_failures)} corpus failure(s), "
        f"{len(result.errors)} error(s)",
        file=out,
    )
