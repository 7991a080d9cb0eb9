"""Serializable AppSpecs: TOML/JSON campaign files + the launch CLI.

A campaign that exists only as Python objects cannot leave its process:
it cannot be launched from a scheduler, diffed against last week's run,
or resumed on another node. This module gives ``AppSpec`` a canonical
plain-dict form (``spec_to_dict``/``spec_from_dict``) and a file form
(``save_spec``/``load_spec``, TOML or JSON by extension), with every
code object — task functions, thinker classes/factories — referenced by
dotted import path::

    [[tasks]]
    fn = "examples.quickstart.simulate"     # @task metadata honored

    [pools.default]
    size = 4
    min_size = 2          # widening the band opts into elasticity
    max_size = 8

    [steering]
    thinker = "examples.quickstart.Quickstart"
    [steering.kwargs]
    n_total = 32

Steering kwargs may reference arbitrary objects with two escapes:
``{"$ref" = "pkg.mod.attr"}`` imports an attribute, and
``{"$call" = "pkg.mod.factory", args = [...], kwargs = {...}}`` calls a
factory — how scenario objects (``repro.surrogate.make_scenario``) reach
a config-file campaign.

An optional ``[smoke]`` table holds overrides deep-merged into the spec
by ``load_spec(path, smoke=True)`` — the campaign file itself declares
its CI-sized form.

The CLI (``python -m repro.app``)::

    python -m repro.app run campaign.toml [--smoke] [--fresh] [--timeout N]
    python -m repro.app show campaign.toml        # normalized JSON (diffable)
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import tomllib
from typing import Any, Dict, List, Mapping, Optional

from .executors import PoolSpec
from .task_server import BatchPolicy, RetryPolicy, StragglerPolicy
from .result import FailureKind
from .thinker import BaseThinker

__all__ = [
    "SPEC_VERSION",
    "diff_spec_dicts",
    "dumps_toml",
    "import_dotted",
    "dotted_path",
    "load_spec",
    "main",
    "save_spec",
    "spec_from_dict",
    "spec_to_dict",
]

# Campaign-file format version. ``spec_to_dict`` stamps it; ``spec_from_dict``
# migrates older versions forward and refuses newer ones with a clear error.
#   v1 (implicit — files with no ``version`` key): allowed the bare-int pool
#      shorthand ``pools.default = 4``.
#   v2: pools must be tables (``pools.default = {size = 4}``); the int
#      shorthand is migrated on load for v1 files but rejected in v2 files,
#      so saved specs are always diffable against what loads.
SPEC_VERSION = 2


# --------------------------------------------------------------------------
# Dotted import paths
# --------------------------------------------------------------------------


def import_dotted(path: str) -> Any:
    """Import ``pkg.mod.attr`` (attr may be nested, e.g. a classmethod
    owner). Raises ``ImportError`` with enough context to fix the config
    file, whichever half failed."""
    if not isinstance(path, str) or not path:
        raise ImportError(f"expected a dotted import path, got {path!r}")
    parts = path.split(".")
    # Longest importable module prefix wins; the rest are attributes.
    module = None
    for i in range(len(parts), 0, -1):
        prefix = ".".join(parts[:i])
        try:
            module = importlib.import_module(prefix)
            attrs = parts[i:]
            break
        except ModuleNotFoundError as exc:
            # Only "this prefix does not exist" shortens the prefix; a
            # module that exists but fails to import (missing dependency,
            # syntax error) must surface its real error, not a confusing
            # "no attribute" fallback.
            if exc.name and (prefix == exc.name or prefix.startswith(exc.name + ".")):
                continue
            raise ImportError(f"cannot import {path!r}: importing {prefix!r} failed: {exc}") from exc
        except ImportError as exc:
            raise ImportError(f"cannot import {path!r}: importing {prefix!r} failed: {exc}") from exc
    if module is None:
        raise ImportError(f"cannot import {path!r}: no importable module prefix")
    obj: Any = module
    for attr in attrs:
        try:
            obj = getattr(obj, attr)
        except AttributeError:
            raise ImportError(
                f"cannot import {path!r}: {obj.__name__ if hasattr(obj, '__name__') else obj!r} "
                f"has no attribute {attr!r}"
            ) from None
    return obj


def dotted_path(obj: Any) -> str:
    """The dotted path that re-imports ``obj``; raises when the object is
    not reachable that way (lambdas, locals, ad-hoc instances)."""
    module = getattr(obj, "__module__", None)
    qualname = getattr(obj, "__qualname__", None)
    if not module or not qualname:
        raise ValueError(
            f"{obj!r} has no importable identity; reference it by module-level "
            "function/class to serialize it"
        )
    if "<locals>" in qualname or "<lambda>" in qualname:
        raise ValueError(
            f"{module}.{qualname} is a local/lambda and cannot be re-imported; "
            "move it to module level to serialize the spec"
        )
    if module == "__main__":
        raise ValueError(
            f"__main__.{qualname} is only importable inside this process; "
            "move it into a module to serialize the spec"
        )
    path = f"{module}.{qualname}"
    try:
        found = import_dotted(path)
    except ImportError as exc:
        raise ValueError(f"{path} does not round-trip: {exc}") from exc
    if found is not obj:
        raise ValueError(f"{path} imports a different object than the one in the spec")
    return path


def _resolve_refs(value: Any) -> Any:
    """Recursively resolve ``$ref``/``$call`` escapes in config values."""
    if isinstance(value, Mapping):
        if "$ref" in value:
            extra = set(value) - {"$ref"}
            if extra:
                raise ValueError(f"$ref takes no other keys (got {sorted(extra)})")
            return import_dotted(value["$ref"])
        if "$call" in value:
            extra = set(value) - {"$call", "args", "kwargs"}
            if extra:
                raise ValueError(f"$call accepts only args/kwargs (got {sorted(extra)})")
            fn = import_dotted(value["$call"])
            args = _resolve_refs(list(value.get("args", ())))
            kwargs = _resolve_refs(dict(value.get("kwargs", {})))
            return fn(*args, **kwargs)
        return {k: _resolve_refs(v) for k, v in value.items()}
    if isinstance(value, list):
        return [_resolve_refs(v) for v in value]
    return value


def _check_plain(value: Any, where: str) -> Any:
    """Require config-file-representable values (str/int/float/bool +
    lists/dicts thereof)."""
    if value is None or isinstance(value, (str, int, float, bool)):
        return value
    if isinstance(value, (list, tuple)):
        return [_check_plain(v, where) for v in value]
    if isinstance(value, Mapping):
        return {str(k): _check_plain(v, f"{where}.{k}") for k, v in value.items()}
    raise ValueError(
        f"{where}: {type(value).__name__} values do not serialize; use a "
        "primitive, or reference the object via {'$ref': ...}/{'$call': ...} "
        "in the config file"
    )


# --------------------------------------------------------------------------
# Spec <-> dict
# --------------------------------------------------------------------------


def spec_to_dict(spec: Any) -> Dict[str, Any]:
    """Canonical plain-dict form of an ``AppSpec`` (JSON/TOML-ready,
    stable for diffing; ``spec_from_dict`` inverts it)."""
    from .app import AppSpec, TaskDef, _as_taskdef  # local: avoid cycle

    if not isinstance(spec, AppSpec):
        raise TypeError(f"expected AppSpec, got {type(spec).__name__}")

    tasks: List[Dict[str, Any]] = []
    for t in spec.tasks:
        td: TaskDef = _as_taskdef(t)
        # method/pool/batch are always explicit so a table entry never
        # falls back to (possibly different) decorator metadata on load.
        entry: Dict[str, Any] = {
            "fn": dotted_path(td.fn),
            "method": td.method,
            "pool": td.pool,
            "batch": td.batch,
        }
        if td.timeout_s is not None:
            entry["timeout_s"] = td.timeout_s
        tasks.append(entry)

    out: Dict[str, Any] = {
        "version": SPEC_VERSION,
        "tasks": tasks,
        "queues": {"backend": spec.queues.backend, "topics": list(spec.queues.topics)},
        "pools": {name: ps.to_dict() for name, ps in sorted(spec.pools.items())},
    }

    if spec.fabric is not None:
        f = spec.fabric
        if not isinstance(f.connector, (str, Mapping)):
            raise ValueError(
                "FabricSpec.connector must be a kind string or spec table to "
                f"serialize (got {type(f.connector).__name__})"
            )
        fab: Dict[str, Any] = {
            "connector": f.connector if isinstance(f.connector, str) else dict(f.connector),
            "threshold": f.threshold,
            "prefetch": f.prefetch,
            "warm_capacity": f.warm_capacity,
            "cache_size": f.cache_size,
        }
        if f.store_name is not None:
            fab["store_name"] = f.store_name
        out["fabric"] = fab

    if spec.observe is None:
        out["observe"] = False
    else:
        o = spec.observe
        if o.log is not None:
            raise ValueError("ObserveSpec.log (a live EventLog) does not serialize")
        if o.reallocator is not None and not isinstance(o.reallocator, str):
            raise ValueError(
                "ObserveSpec.reallocator must be 'greedy'/'ema' to serialize "
                f"(got {type(o.reallocator).__name__})"
            )
        obs: Dict[str, Any] = {"capacity": o.capacity}
        if o.jsonl_path is not None:
            obs["jsonl_path"] = o.jsonl_path
        if o.server_jsonl_path is not None:
            obs["server_jsonl_path"] = o.server_jsonl_path
        if o.rotate_bytes is not None:
            obs["rotate_bytes"] = o.rotate_bytes
            obs["rotate_keep"] = o.rotate_keep
        if o.export is not None:
            if isinstance(o.export, str):
                obs["export"] = o.export
            elif isinstance(o.export, Mapping):
                obs["export"] = dict(o.export)
            elif hasattr(o.export, "to_dict"):
                obs["export"] = o.export.to_dict()
            else:
                raise ValueError(
                    f"ObserveSpec.export {type(o.export).__name__} does not serialize"
                )
        if o.reallocator is not None:
            obs["reallocator"] = o.reallocator
            obs["realloc_interval"] = o.realloc_interval
        if o.realloc_min_slots:
            obs["realloc_min_slots"] = dict(o.realloc_min_slots)
        if o.elastic is not None:
            if o.elastic is True:
                obs["elastic"] = {}
            elif isinstance(o.elastic, Mapping):
                obs["elastic"] = dict(o.elastic)
            elif hasattr(o.elastic, "to_dict"):
                obs["elastic"] = o.elastic.to_dict()
            else:
                raise ValueError(
                    f"ObserveSpec.elastic {type(o.elastic).__name__} does not serialize"
                )
        if o.ops_port is not None:
            obs["ops_port"] = o.ops_port
        if o.remediate:
            obs["remediate"] = True
        for knob in ("slo", "anomaly"):
            v = getattr(o, knob)
            if v is None:
                continue
            if v is True:
                obs[knob] = {}
            elif isinstance(v, Mapping):
                obs[knob] = dict(v)
            elif hasattr(v, "to_dict"):
                obs[knob] = v.to_dict()
            else:
                raise ValueError(
                    f"ObserveSpec.{knob} {type(v).__name__} does not serialize"
                )
        out["observe"] = obs

    if spec.steering is not None:
        out["steering"] = {
            "thinker": dotted_path(spec.steering.thinker),
            "kwargs": _check_plain(spec.steering.kwargs, "steering.kwargs"),
        }

    if spec.campaign is not None:
        c = spec.campaign
        out["campaign"] = {
            "state_dir": c.state_dir,
            "checkpoint_interval_s": c.checkpoint_interval_s,
            "name": c.name,
            "resume": c.resume,
        }

    if spec.control is not None:
        ctl = spec.control
        control: Dict[str, Any] = {
            "weight": ctl.weight,
            "priority": ctl.priority,
            "min_slots": ctl.min_slots,
        }
        if ctl.demand is not None:
            control["demand"] = ctl.demand
        out["control"] = control

    s = spec.server
    if s.injector is not None:
        raise ValueError("ServerSpec.injector (a FailureInjector) does not serialize")
    server: Dict[str, Any] = {
        "in_process": s.in_process,
        "max_batch": s.max_batch,
        "linger_s": s.linger_s,
        "heartbeat_timeout_s": s.heartbeat_timeout_s,
    }
    if s.retry is not None:
        server["retry"] = {
            "max_retries": s.retry.max_retries,
            "backoff_s": s.retry.backoff_s,
            "retry_on": [k.name for k in s.retry.retry_on],
        }
    if s.straggler is not None:
        server["straggler"] = {
            "enabled": s.straggler.enabled,
            "factor": s.straggler.factor,
            "min_history": s.straggler.min_history,
            "check_interval_s": s.straggler.check_interval_s,
        }
    if s.batching is not None:
        b: Dict[str, Any] = {"max_batch": s.batching.max_batch, "linger_s": s.batching.linger_s}
        if s.batching.methods is not None:
            b["methods"] = list(s.batching.methods)
        server["batching"] = b
    out["server"] = server
    return out


def _task_from_entry(entry: Any) -> Any:
    from .app import TaskDef, _as_taskdef  # local: avoid cycle

    if isinstance(entry, str):
        return _as_taskdef(import_dotted(entry))
    if not isinstance(entry, Mapping):
        raise TypeError(f"task entry must be a dotted path or table, got {type(entry).__name__}")
    if "fn" not in entry:
        raise ValueError(f"task entry needs an 'fn' dotted path (got keys {sorted(entry)})")
    unknown = set(entry) - {"fn", "method", "pool", "timeout_s", "batch"}
    if unknown:
        raise ValueError(
            f"task entry {entry['fn']!r}: unknown keys {sorted(unknown)}"
        )
    fn = import_dotted(entry["fn"])
    base = _as_taskdef(fn)  # honors @task decorator metadata
    return TaskDef(
        fn=base.fn,
        method=entry.get("method", base.method),
        pool=entry.get("pool", base.pool),
        timeout_s=entry.get("timeout_s", base.timeout_s),
        batch=entry.get("batch", base.batch),
    )


def _spec_version(d: Mapping[str, Any]) -> int:
    """Validate the ``version`` key; files without one are v1 (the format
    that predates versioning). Future versions fail loudly rather than
    half-loading a file written by a newer build."""
    v = d.get("version", 1)
    if isinstance(v, bool) or not isinstance(v, int):
        raise ValueError(f"spec version must be an integer (got {v!r})")
    if v < 1:
        raise ValueError(f"spec version must be >= 1 (got {v})")
    if v > SPEC_VERSION:
        raise ValueError(
            f"campaign spec declares version {v}, but this build reads "
            f"version <= {SPEC_VERSION} — upgrade repro, or re-save the "
            "spec from the build that wrote it"
        )
    return v


def _migrate_spec_dict(d: Mapping[str, Any], version: int) -> Dict[str, Any]:
    """Rewrite a pre-``SPEC_VERSION`` dict into the current shape.
    v1 -> v2: the bare-int pool shorthand becomes an explicit table."""
    out = dict(d)
    if version < 2 and isinstance(out.get("pools"), Mapping):
        out["pools"] = {
            name: ({"size": v} if isinstance(v, int) and not isinstance(v, bool) else v)
            for name, v in out["pools"].items()
        }
    return out


def spec_from_dict(d: Mapping[str, Any]) -> Any:
    """Build an ``AppSpec`` from its plain-dict form (inverse of
    ``spec_to_dict``; also accepts hand-written config shorthands).
    Pre-``SPEC_VERSION`` dicts are migrated forward on the fly."""
    from .app import (  # local: avoid cycle
        AppSpec,
        CampaignSpec,
        ControlSpec,
        FabricSpec,
        ObserveSpec,
        QueueSpec,
        ServerSpec,
        SteeringSpec,
    )

    known = {"version", "tasks", "queues", "pools", "fabric", "observe",
             "steering", "campaign", "server", "control", "smoke"}
    unknown = set(d) - known
    if unknown:
        raise ValueError(f"unknown spec sections: {sorted(unknown)}")
    version = _spec_version(d)
    d = _migrate_spec_dict(d, version)
    if version >= 2 and isinstance(d.get("pools"), Mapping):
        bare = sorted(name for name, v in d["pools"].items()
                      if isinstance(v, int) and not isinstance(v, bool))
        if bare:
            raise ValueError(
                f"pools {bare}: version {version} specs spell pool sizes as "
                "tables ({size = n}); the bare-int shorthand is only read "
                "from version 1 (unversioned) files"
            )
    if "tasks" not in d or not d["tasks"]:
        raise ValueError("a campaign needs at least one [[tasks]] entry")

    tasks = [_task_from_entry(t) for t in d["tasks"]]

    q = d.get("queues", "local")
    if isinstance(q, str):
        queues: Any = q
    else:
        unknown_q = set(q) - {"backend", "topics"}
        if unknown_q:
            raise ValueError(f"queues: unknown keys {sorted(unknown_q)}")
        queues = QueueSpec(
            backend=q.get("backend", "local"), topics=tuple(q.get("topics", ("default",)))
        )

    pools = None
    if "pools" in d:
        pools = {name: PoolSpec.from_dict(name, v) for name, v in d["pools"].items()}

    fabric = None
    if "fabric" in d and d["fabric"] is not False:
        f = dict(d["fabric"])
        fabric = FabricSpec(**f)

    observe: Optional[ObserveSpec]
    o = d.get("observe", {})
    if o is False:
        observe = None
    else:
        o = dict(o)
        if "elastic" in o and o["elastic"] is not False:
            o["elastic"] = dict(o["elastic"]) if isinstance(o["elastic"], Mapping) else o["elastic"]
        elif o.get("elastic") is False:
            o.pop("elastic")
        for knob in ("slo", "anomaly"):
            # `slo = false` in a [smoke] override disables the engine the
            # same way `elastic = false` disables the scaler.
            if o.get(knob) is False:
                o.pop(knob)
            elif knob in o and isinstance(o[knob], Mapping):
                o[knob] = dict(o[knob])
        observe = ObserveSpec(**o)

    steering = None
    if "steering" in d:
        st = d["steering"]
        thinker = import_dotted(st["thinker"])
        if not callable(thinker):
            raise ValueError(
                f"steering.thinker {st['thinker']!r} is not a BaseThinker subclass "
                "or factory callable"
            )
        steering = SteeringSpec(thinker, _resolve_refs(dict(st.get("kwargs", {}))))

    campaign = None
    if "campaign" in d:
        campaign = CampaignSpec(**dict(d["campaign"]))

    control = None
    if "control" in d:
        control = ControlSpec(**dict(d["control"]))

    server = ServerSpec()
    if "server" in d:
        s = dict(d["server"])
        if "retry" in s:
            r = dict(s["retry"])
            if "retry_on" in r:
                r["retry_on"] = tuple(FailureKind[name] for name in r["retry_on"])
            s["retry"] = RetryPolicy(**r)
        if "straggler" in s:
            s["straggler"] = StragglerPolicy(**dict(s["straggler"]))
        if "batching" in s:
            b = dict(s["batching"])
            if "methods" in b:
                b["methods"] = tuple(b["methods"])
            s["batching"] = BatchPolicy(**b)
        server = ServerSpec(**s)

    return AppSpec(
        tasks=tasks,
        steering=steering,
        queues=queues,
        pools=pools,
        fabric=fabric,
        observe=observe,
        campaign=campaign,
        server=server,
        control=control,
    )


# --------------------------------------------------------------------------
# TOML (write: minimal emitter for the spec subset; read: tomllib)
# --------------------------------------------------------------------------


def _toml_key(k: str) -> str:
    if k and all(c.isalnum() or c in "-_" for c in k):
        return k
    return '"' + k.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _toml_scalar(v: Any) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, int):
        return str(v)
    if isinstance(v, float):
        s = repr(v)
        return s if any(c in s for c in ".eE") else s + ".0"
    if isinstance(v, str):
        return json.dumps(v)  # valid TOML basic string
    if isinstance(v, (list, tuple)):
        return "[" + ", ".join(_toml_scalar(x) for x in v) + "]"
    if isinstance(v, Mapping):
        inner = ", ".join(f"{_toml_key(k)} = {_toml_scalar(x)}" for k, x in v.items())
        return "{" + inner + "}"
    raise TypeError(f"cannot write {type(v).__name__} to TOML")


def _emit_table(d: Mapping[str, Any], prefix: List[str], lines: List[str]) -> None:
    scalars = {k: v for k, v in d.items()
               if not isinstance(v, Mapping)
               and not (isinstance(v, list) and v and all(isinstance(x, Mapping) for x in v))}
    tables = {k: v for k, v in d.items() if isinstance(v, Mapping)}
    arrays = {k: v for k, v in d.items()
              if isinstance(v, list) and v and all(isinstance(x, Mapping) for x in v)}
    if prefix and (scalars or not (tables or arrays)):
        lines.append("[" + ".".join(_toml_key(p) for p in prefix) + "]")
    for k, v in scalars.items():
        lines.append(f"{_toml_key(k)} = {_toml_scalar(v)}")
    if scalars or (prefix and not (tables or arrays)):
        lines.append("")
    for k, rows in arrays.items():
        header = ".".join(_toml_key(p) for p in prefix + [k])
        for row in rows:
            lines.append(f"[[{header}]]")
            for rk, rv in row.items():
                lines.append(f"{_toml_key(rk)} = {_toml_scalar(rv)}")
            lines.append("")
    for k, v in tables.items():
        _emit_table(v, prefix + [k], lines)


def dumps_toml(d: Mapping[str, Any]) -> str:
    """Serialize a spec dict as TOML (round-trips through ``tomllib``)."""
    lines: List[str] = []
    _emit_table(d, [], lines)
    return "\n".join(lines).rstrip() + "\n"


def _load_toml(path: str) -> Dict[str, Any]:
    with open(path, "rb") as f:
        return tomllib.load(f)


# --------------------------------------------------------------------------
# Files
# --------------------------------------------------------------------------


def _deep_merge(base: Dict[str, Any], override: Mapping[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in override.items():
        if isinstance(v, Mapping) and isinstance(out.get(k), Mapping):
            out[k] = _deep_merge(dict(out[k]), v)
        else:
            out[k] = v
    return out


def load_spec(path: str, smoke: bool = False) -> Any:
    """Load a TOML/JSON campaign file into an ``AppSpec``. ``smoke=True``
    deep-merges the file's ``[smoke]`` table over the spec first (the
    file's own CI-sized form)."""
    if path.endswith(".json"):
        with open(path) as f:
            d = json.load(f)
    elif path.endswith(".toml"):
        d = _load_toml(path)
    else:
        raise ValueError(f"campaign file must be .toml or .json (got {path!r})")
    overrides = d.pop("smoke", None)
    if smoke:
        if not overrides:
            raise ValueError(f"{path} has no [smoke] table; cannot apply --smoke")
        d = _deep_merge(d, overrides)
    return spec_from_dict(d)


def save_spec(spec: Any, path: str) -> str:
    """Write the spec as TOML or JSON (by extension); returns the path."""
    d = spec_to_dict(spec)
    if path.endswith(".json"):
        body = json.dumps(d, indent=2, sort_keys=True) + "\n"
    elif path.endswith(".toml"):
        body = dumps_toml(d)
    else:
        raise ValueError(f"campaign file must be .toml or .json (got {path!r})")
    with open(path, "w") as f:
        f.write(body)
    return path


# --------------------------------------------------------------------------
# Spec diff: field-aware comparison of two campaign files
# --------------------------------------------------------------------------


def _load_raw(path: str, smoke: bool = False) -> Dict[str, Any]:
    """Load a campaign file as a raw dict (no import of task modules) so
    ``diff`` works even when a spec's ``fn`` targets are unimportable."""
    if path.endswith(".json"):
        with open(path) as f:
            d = json.load(f)
    elif path.endswith(".toml"):
        d = _load_toml(path)
    else:
        raise ValueError(f"campaign file must be .toml or .json (got {path!r})")
    overrides = d.pop("smoke", None)
    if smoke:
        if not overrides:
            raise ValueError(f"{path} has no [smoke] table; cannot apply --smoke")
        d = _deep_merge(d, overrides)
    return d


def _render_value(v: Any) -> str:
    """Human-readable rendering for diff lines: ``$ref``/``$call`` markers
    print as calls rather than opaque nested dicts."""
    if isinstance(v, Mapping):
        if "$ref" in v:
            return f"$ref({v['$ref']})"
        if "$call" in v:
            parts = [repr(a) for a in v.get("args", ())]
            parts += [f"{k}={r!r}" for k, r in v.get("kwargs", {}).items()]
            return f"$call({v['$call']})({', '.join(parts)})"
    return json.dumps(v, sort_keys=True, default=repr)


def _is_marker(v: Any) -> bool:
    return isinstance(v, Mapping) and ("$ref" in v or "$call" in v)


def _flatten_spec(d: Any, prefix: str = "") -> Dict[str, Any]:
    """Flatten a spec dict to ``dotted.path -> leaf`` pairs. ``$ref``/
    ``$call`` tables are leaves. The ``[[tasks]]`` array is keyed by each
    entry's method/fn name when unambiguous, so reordering tasks does not
    diff and per-task field changes anchor to the task's name."""
    flat: Dict[str, Any] = {}
    if isinstance(d, Mapping) and not _is_marker(d):
        if not d:
            flat[prefix] = {}
        for k, v in d.items():
            flat.update(_flatten_spec(v, f"{prefix}.{k}" if prefix else str(k)))
        return flat
    if isinstance(d, list) and d and all(isinstance(x, Mapping) for x in d) \
            and not any(_is_marker(x) for x in d):
        names = [x.get("method") or x.get("fn") for x in d]
        use_names = all(names) and len(set(names)) == len(names)
        for i, v in enumerate(d):
            key = names[i] if use_names else str(i)
            flat.update(_flatten_spec(v, f"{prefix}[{key}]"))
        return flat
    flat[prefix] = d
    return flat


def diff_spec_dicts(a: Mapping[str, Any], b: Mapping[str, Any]) -> List[str]:
    """Field-aware diff of two raw spec dicts. Returns human-readable
    lines (``~`` changed, ``+`` only in b, ``-`` only in a); empty means
    the specs are equivalent after migration to the current version."""
    lines: List[str] = []
    va, vb = _spec_version(a), _spec_version(b)
    if va != vb:
        note = []
        if va < SPEC_VERSION:
            note.append("a migrated")
        if vb < SPEC_VERSION:
            note.append("b migrated")
        suffix = f" ({', '.join(note)} to v{SPEC_VERSION} for comparison)" if note else ""
        lines.append(f"~ version: {va} -> {vb}{suffix}")
    fa = _flatten_spec(_migrate_spec_dict(a, va))
    fb = _flatten_spec(_migrate_spec_dict(b, vb))
    fa.pop("version", None)
    fb.pop("version", None)
    for path in sorted(set(fa) | set(fb)):
        if path not in fb:
            lines.append(f"- {path} = {_render_value(fa[path])}")
        elif path not in fa:
            lines.append(f"+ {path} = {_render_value(fb[path])}")
        elif fa[path] != fb[path]:
            lines.append(f"~ {path}: {_render_value(fa[path])} -> {_render_value(fb[path])}")
    return lines


# --------------------------------------------------------------------------
# CLI: python -m repro.app run campaign.toml
# --------------------------------------------------------------------------


def _cmd_run(args: argparse.Namespace) -> int:
    from .app import ColmenaApp

    spec = load_spec(args.path, smoke=args.smoke)
    if args.fresh and spec.campaign is not None:
        spec.campaign.resume = False
    if args.resume and spec.campaign is None:
        print("error: --resume needs a [campaign] section", file=sys.stderr)
        return 2
    app = ColmenaApp(spec)
    report = app.execute(timeout=args.timeout)
    print(f"campaign,completed,{int(report.completed)}")
    print(f"campaign,wall_seconds,{report.wall_seconds:.2f}")
    print(f"campaign,checkpoints_written,{report.checkpoints_written}")
    print(f"campaign,resumed_from,{report.resumed_from or ''}")
    print(f"campaign,tasks_completed,{report.server_metrics.get('tasks_completed', 0)}")
    obs = app.observe_report()
    if obs:
        print(f"campaign,makespan_s,{obs.get('makespan_s', 0.0)}")
        for pool, u in sorted(obs.get("utilization", {}).items()):
            print(f"utilization,{pool},{u}")
    return 0 if report.completed else 1


def _cmd_show(args: argparse.Namespace) -> int:
    spec = load_spec(args.path, smoke=args.smoke)
    print(json.dumps(spec_to_dict(spec), indent=2, sort_keys=True))
    return 0


def _cmd_diff(args: argparse.Namespace) -> int:
    a = _load_raw(args.a, smoke=args.smoke)
    b = _load_raw(args.b, smoke=args.smoke)
    lines = diff_spec_dicts(a, b)
    for line in lines:
        print(line)
    if not lines:
        print(f"specs are equivalent: {args.a} == {args.b}")
        return 0
    return 1


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.app",
        description="Launch or inspect a Colmena campaign defined in a TOML/JSON file.",
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    run = sub.add_parser("run", help="compose and run the campaign")
    run.add_argument("path", help="campaign .toml or .json file")
    run.add_argument("--smoke", action="store_true",
                     help="apply the file's [smoke] override table")
    run.add_argument("--resume", action="store_true",
                     help="require a [campaign] section (resume is its default)")
    run.add_argument("--fresh", action="store_true",
                     help="ignore existing checkpoints (resume=False)")
    run.add_argument("--timeout", type=float, default=None,
                     help="wall-clock bound for the steering agents")
    run.set_defaults(fn=_cmd_run)

    show = sub.add_parser("show", help="print the normalized spec as JSON (diffable)")
    show.add_argument("path")
    show.add_argument("--smoke", action="store_true")
    show.set_defaults(fn=_cmd_show)

    diff = sub.add_parser(
        "diff", help="field-aware diff of two campaign files (exit 1 when they differ)"
    )
    diff.add_argument("a")
    diff.add_argument("b")
    diff.add_argument("--smoke", action="store_true",
                      help="apply each file's [smoke] override table before diffing")
    diff.set_defaults(fn=_cmd_diff)

    args = ap.parse_args(argv)
    try:
        return args.fn(args)
    except BrokenPipeError:
        # Output is CSV-ish lines meant for `| head` / `| grep -q`;
        # a consumer closing the pipe early is not a campaign failure.
        try:
            sys.stdout.close()
        except Exception:  # noqa: BLE001
            pass
        return 0
