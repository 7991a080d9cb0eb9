"""Serializable AppSpecs: dict/TOML/JSON round-trips + the launch CLI.

Everything config-file launch depends on: ``spec_to_dict`` /
``spec_from_dict`` inversion (including dotted-path task/thinker
resolution and the error messages bad paths produce), the TOML writer
round-tripping through a real TOML parser, ``$ref``/``$call`` escapes,
``[smoke]`` overrides, resume-through-a-config-file, and the
``python -m repro.app`` CLI end to end.
"""

import json
import os
import subprocess
import sys
import tomllib

import pytest

from repro.app import (
    AppSpec,
    CampaignSpec,
    ColmenaApp,
    FabricSpec,
    ObserveSpec,
    PoolSpec,
    QueueSpec,
    ServerSpec,
    SteeringSpec,
    TaskDef,
    load_spec,
    save_spec,
    spec_from_dict,
    spec_to_dict,
    task,
)
from repro.core import BaseThinker, ResourceCounter, RetryPolicy, agent, result_processor
from repro.core.specfile import SPEC_VERSION, dotted_path, dumps_toml, import_dotted

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SENTINEL = object()


@task(pool="special", timeout_s=2.5)
def special_task(x):
    return x + 1


def plain_task(x):
    return 2 * x


class ConfigThinker(BaseThinker):
    """Checkpointable submit-on-completion thinker for config launches."""

    def __init__(self, queues, target=6, n_parallel=2, sentinel=None):
        super().__init__(queues, ResourceCounter(n_parallel))
        self.target = target
        self.sentinel = sentinel
        self.count = 0

    @agent(startup=True)
    def boot(self):
        for _ in range(self.rec.total_slots):
            self.queues.send_inputs(1, method="double")

    @result_processor()
    def recv(self, result):
        self.count += 1
        if self.count >= self.target:
            self.done.set()
        else:
            self.queues.send_inputs(1, method="double")

    def get_state(self):
        return {"count": self.count}

    def set_state(self, state):
        self.count = state.get("count", 0)


def _full_spec():
    return AppSpec(
        tasks=[TaskDef(fn=plain_task, method="double"), special_task],
        queues=QueueSpec(backend="local", topics=("default", "aux")),
        pools={
            "default": PoolSpec("default", 2, min_size=1, max_size=4),
            "special": 1,
        },
        fabric=FabricSpec(connector="memory", threshold=5000, warm_capacity=16),
        observe=ObserveSpec(capacity=4096, elastic={"interval": 0.02}),
        steering=SteeringSpec(ConfigThinker, dict(target=4, n_parallel=2)),
        server=ServerSpec(retry=RetryPolicy(max_retries=3, backoff_s=0.01)),
    )


class TestDictRoundTrip:
    def test_to_dict_from_dict_fixed_point(self):
        spec = _full_spec()
        d = spec_to_dict(spec)
        spec2 = spec_from_dict(d)
        assert spec_to_dict(spec2) == d

    def test_toml_round_trip_through_real_parser(self):
        d = spec_to_dict(_full_spec())
        parsed = tomllib.loads(dumps_toml(d))
        assert spec_to_dict(spec_from_dict(parsed)) == d

    def test_file_round_trip_toml_and_json(self, tmp_path):
        spec = _full_spec()
        for name in ("campaign.toml", "campaign.json"):
            path = str(tmp_path / name)
            save_spec(spec, path)
            assert spec_to_dict(load_spec(path)) == spec_to_dict(spec)

    def test_task_decorator_metadata_survives(self):
        spec2 = spec_from_dict(spec_to_dict(_full_spec()))
        tds = {t.method: t for t in spec2.tasks}
        assert tds["special_task"].pool == "special"
        assert tds["special_task"].timeout_s == 2.5

    def test_bare_string_task_honors_decorator(self):
        spec = spec_from_dict({
            "tasks": ["test_config_launch.special_task"],
            "pools": {"special": 1},
        })
        td = spec.tasks[0]
        assert td.pool == "special" and td.timeout_s == 2.5

    def test_loaded_spec_actually_runs(self, tmp_path):
        path = str(tmp_path / "c.toml")
        save_spec(_full_spec(), path)
        app = ColmenaApp(load_spec(path))
        with app.run(timeout=30) as handle:
            assert handle.wait(30)
        assert handle.thinker.count == 4
        assert app.report.completed


class TestSpecVersioning:
    def test_to_dict_stamps_current_version(self):
        assert spec_to_dict(_full_spec())["version"] == SPEC_VERSION

    def test_saved_files_carry_the_version(self, tmp_path):
        path = str(tmp_path / "c.toml")
        save_spec(_full_spec(), path)
        assert f"version = {SPEC_VERSION}" in open(path).read()

    def test_v1_int_pool_shorthand_migrates(self):
        # a pre-versioning file: no version key, bare-int pool sizes
        spec = spec_from_dict({
            "tasks": ["test_config_launch.special_task"],
            "pools": {"special": 3},
        })
        assert spec.pools["special"].size == 3

    def test_v2_rejects_int_pool_shorthand(self):
        with pytest.raises(ValueError, match="bare-int shorthand"):
            spec_from_dict({
                "version": 2,
                "tasks": ["test_config_launch.special_task"],
                "pools": {"special": 3},
            })

    def test_future_version_fails_loudly(self):
        with pytest.raises(ValueError, match="upgrade repro"):
            spec_from_dict({
                "version": SPEC_VERSION + 1,
                "tasks": ["test_config_launch.special_task"],
            })

    @pytest.mark.parametrize("bad", ["2", True, 0, -1, 1.5])
    def test_malformed_version_rejected(self, bad):
        with pytest.raises(ValueError, match="version"):
            spec_from_dict({
                "version": bad,
                "tasks": ["test_config_launch.special_task"],
            })

    def test_versioned_file_load(self, tmp_path):
        # save (stamps v2) -> load honors the stamp and round-trips
        path = str(tmp_path / "c.json")
        save_spec(_full_spec(), path)
        doc = json.load(open(path))
        assert doc["version"] == SPEC_VERSION
        assert spec_to_dict(load_spec(path)) == spec_to_dict(_full_spec())

    def test_v1_file_still_loads(self, tmp_path):
        # a legacy file written before versioning existed
        path = str(tmp_path / "old.json")
        doc = spec_to_dict(_full_spec())
        del doc["version"]
        doc["pools"]["special"] = 1  # the old shorthand
        json.dump(doc, open(path, "w"))
        spec = load_spec(path)
        assert spec.pools["special"].size == 1


class TestDottedPaths:
    def test_import_dotted_resolves_nested_attr(self):
        assert import_dotted("repro.core.PoolSpec") is PoolSpec

    def test_import_dotted_bad_module(self):
        with pytest.raises(ImportError, match="no importable module prefix"):
            import_dotted("no_such_pkg_xyz.mod.fn")

    def test_import_dotted_bad_attr_names_the_culprit(self):
        with pytest.raises(ImportError, match="has no attribute 'nope'"):
            import_dotted("repro.core.nope")

    def test_broken_module_surfaces_its_real_error(self, tmp_path, monkeypatch):
        """A module that exists but fails to import must report its own
        error, not a misleading 'no attribute' fallback."""
        (tmp_path / "broken_cfg_mod.py").write_text("import no_such_dep_xyz\n")
        monkeypatch.syspath_prepend(str(tmp_path))
        with pytest.raises(ImportError, match="no_such_dep_xyz"):
            import_dotted("broken_cfg_mod.fn")

    def test_local_function_rejected_with_fix_hint(self):
        def local_fn(x):
            return x

        with pytest.raises(ValueError, match="local/lambda"):
            dotted_path(local_fn)

    def test_lambda_rejected(self):
        with pytest.raises(ValueError, match="local/lambda"):
            spec_to_dict(AppSpec(tasks={"f": lambda x: x}))

    def test_spec_from_dict_bad_task_path(self):
        with pytest.raises(ImportError, match="no importable module prefix"):
            spec_from_dict({"tasks": ["nowhere_at_all.fn"]})

    def test_unknown_sections_rejected(self):
        with pytest.raises(ValueError, match="unknown spec sections"):
            spec_from_dict({"tasks": ["test_config_launch.plain_task"], "poolz": {}})

    def test_unknown_queue_keys_rejected(self):
        with pytest.raises(ValueError, match=r"queues: unknown keys \['backand'\]"):
            spec_from_dict({
                "tasks": ["test_config_launch.plain_task"],
                "queues": {"backand": "pipe"},
            })

    def test_unknown_task_keys_rejected(self):
        """A typo like timeout= (for timeout_s=) must not silently drop
        the setting."""
        with pytest.raises(ValueError, match=r"unknown keys \['timeout'\]"):
            spec_from_dict({
                "tasks": [{"fn": "test_config_launch.plain_task", "timeout": 5}],
            })


class TestRefsAndSmoke:
    def test_ref_and_call_escapes(self):
        spec = spec_from_dict({
            "tasks": [{"fn": "test_config_launch.plain_task", "method": "double"}],
            "steering": {
                "thinker": "test_config_launch.ConfigThinker",
                "kwargs": {
                    "sentinel": {"$ref": "test_config_launch.SENTINEL"},
                    "target": {"$call": "builtins.int", "args": ["7"]},
                },
            },
        })
        assert spec.steering.kwargs["sentinel"] is SENTINEL
        assert spec.steering.kwargs["target"] == 7

    def test_ref_with_extra_keys_rejected(self):
        with pytest.raises(ValueError, match=r"\$ref takes no other keys"):
            spec_from_dict({
                "tasks": ["test_config_launch.plain_task"],
                "steering": {"thinker": "test_config_launch.ConfigThinker",
                             "kwargs": {"x": {"$ref": "os.sep", "junk": 1}}},
            })

    def test_unserializable_kwargs_point_to_escapes(self):
        spec = AppSpec(
            tasks={"double": plain_task},
            steering=SteeringSpec(ConfigThinker, dict(sentinel=object())),
        )
        with pytest.raises(ValueError, match=r"\$ref"):
            spec_to_dict(spec)

    def test_smoke_overrides_deep_merge(self, tmp_path):
        path = str(tmp_path / "c.toml")
        with open(path, "w") as f:
            f.write(
                '[[tasks]]\nfn = "test_config_launch.plain_task"\nmethod = "double"\n\n'
                + '[steering]\nthinker = "test_config_launch.ConfigThinker"\n'
                + '[steering.kwargs]\ntarget = 40\nn_parallel = 2\n\n'
                + '[smoke.steering.kwargs]\ntarget = 3\n'
            )
        full = load_spec(path)
        smoke = load_spec(path, smoke=True)
        assert full.steering.kwargs["target"] == 40
        assert smoke.steering.kwargs["target"] == 3
        assert smoke.steering.kwargs["n_parallel"] == 2  # merged, not replaced

    def test_smoke_flag_without_table_errors(self, tmp_path):
        path = str(tmp_path / "c.toml")
        with open(path, "w") as f:
            f.write('[[tasks]]\nfn = "test_config_launch.plain_task"\n')
        with pytest.raises(ValueError, match="no \\[smoke\\] table"):
            load_spec(path, smoke=True)


class TestConfigResume:
    def test_resume_through_config_file(self, tmp_path):
        """The checkpoint/resume path driven purely from a saved file."""
        state_dir = str(tmp_path / "state")
        cfg = str(tmp_path / "c.json")
        spec = AppSpec(
            tasks=[TaskDef(fn=plain_task, method="double")],
            pools={"default": 2},
            steering=SteeringSpec(ConfigThinker, dict(target=4)),
            campaign=CampaignSpec(state_dir=state_dir, checkpoint_interval_s=0.2),
        )
        save_spec(spec, cfg)

        first = ColmenaApp(load_spec(cfg))
        first.execute(timeout=30)
        assert first.thinker.count == 4
        assert first.report.checkpoints_written >= 1

        second_spec = load_spec(cfg)
        second_spec.steering.kwargs["target"] = 8
        second = ColmenaApp(second_spec)
        second.execute(timeout=30)
        assert second.report.resumed_from is not None
        assert second.thinker.count == 8


@pytest.mark.skipif(not os.path.isdir(os.path.join(REPO_ROOT, "examples")),
                    reason="examples/ not present")
class TestCLI:
    def _run_cli(self, *args, timeout=120):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src") + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        return subprocess.run(
            [sys.executable, "-m", "repro.app", *args],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=timeout,
        )

    def test_run_quickstart_toml_smoke(self):
        proc = self._run_cli("run", "examples/quickstart.toml", "--smoke")
        assert proc.returncode == 0, proc.stderr
        assert "campaign,completed,1" in proc.stdout

    def test_show_is_diffable_json(self):
        proc = self._run_cli("show", "examples/quickstart.toml")
        assert proc.returncode == 0, proc.stderr
        d = json.loads(proc.stdout)
        assert d["steering"]["thinker"] == "examples.quickstart.Quickstart"
        assert d["pools"]["default"]["size"] == 4


class TestSpecDiff:
    """`python -m repro.app diff a.toml b.toml`: field-aware, version-
    stamp aware, with $ref/$call rendered readably."""

    A = """
version = 2
[[tasks]]
fn = "math.sin"
timeout_s = 5
[pools.default]
size = 4
[control]
weight = 2.0
"""
    B = """
version = 2
[[tasks]]
fn = "math.sin"
[[tasks]]
fn = "math.cos"
[pools.default]
size = 2
[control]
weight = 2.0
priority = 1
"""

    def test_diff_lines_are_field_aware(self):
        from repro.core.specfile import diff_spec_dicts

        lines = diff_spec_dicts(tomllib.loads(self.A), tomllib.loads(self.B))
        assert "~ pools.default.size: 4 -> 2" in lines
        assert "- tasks[math.sin].timeout_s = 5" in lines
        assert any(line.startswith("+ tasks[math.cos].fn") for line in lines)
        assert "+ control.priority = 1" in lines
        assert not any("weight" in line for line in lines)  # unchanged field

    def test_identical_specs_diff_empty(self):
        from repro.core.specfile import diff_spec_dicts

        assert diff_spec_dicts(tomllib.loads(self.A), tomllib.loads(self.A)) == []

    def test_cli_exit_codes_and_output(self, tmp_path):
        a = tmp_path / "a.toml"
        b = tmp_path / "b.toml"
        a.write_text(self.A)
        b.write_text(self.B)
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO_ROOT, "src"))
        run = subprocess.run(
            [sys.executable, "-m", "repro.app", "diff", str(a), str(b)],
            capture_output=True, text=True, env=env,
        )
        assert run.returncode == 1  # differences found
        assert "pools.default.size" in run.stdout
        same = subprocess.run(
            [sys.executable, "-m", "repro.app", "diff", str(a), str(a)],
            capture_output=True, text=True, env=env,
        )
        assert same.returncode == 0
        assert "equivalent" in same.stdout

    def test_version_migration_is_reported_not_diffed(self, tmp_path):
        """A v1 file (int pool shorthand) diffed against its v2 twin is
        equivalent apart from the version note."""
        from repro.core.specfile import diff_spec_dicts

        v1 = "version = 1\n[[tasks]]\nfn = \"math.sin\"\n[pools]\ndefault = 4\n"
        v2 = "version = 2\n[[tasks]]\nfn = \"math.sin\"\n[pools.default]\nsize = 4\n"
        lines = diff_spec_dicts(tomllib.loads(v1), tomllib.loads(v2))
        assert lines and lines[0].startswith("~ version: 1 -> 2")
        assert len(lines) == 1  # migrated bodies agree
