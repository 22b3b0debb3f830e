"""CLI verb tests (reference Console scope, SURVEY.md section 2.4)."""

import os

from predictionio_tpu.tools.cli import main

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(capsys, *argv):
    code = main(list(argv))
    return code, capsys.readouterr().out


class TestAppVerbs:
    def test_app_lifecycle(self, storage_env, capsys):
        code, out = run(capsys, "app", "new", "Shop")
        assert code == 0
        assert "Access Key:" in out and "ID: 1" in out

        code, out = run(capsys, "app", "new", "Shop")
        assert code == 1  # duplicate

        code, out = run(capsys, "app", "list")
        assert "Shop" in out

        code, out = run(capsys, "app", "show", "Shop")
        assert "Name: Shop" in out

        code, out = run(capsys, "app", "delete", "Shop", "--force")
        assert code == 0
        code, out = run(capsys, "app", "list")
        assert "Shop" not in out

    def test_channels(self, storage_env, capsys):
        run(capsys, "app", "new", "A")
        code, out = run(capsys, "app", "channel-new", "A", "backtest")
        assert code == 0
        code, out = run(capsys, "app", "channel-new", "A", "bad name")
        assert code == 1
        code, out = run(capsys, "app", "show", "A")
        assert "Channel: backtest" in out
        code, out = run(capsys, "app", "channel-delete", "A", "backtest", "--force")
        assert code == 0

    def test_accesskeys(self, storage_env, capsys):
        run(capsys, "app", "new", "A")
        code, out = run(capsys, "accesskey", "new", "A", "view", "buy")
        assert code == 0
        key = out.strip().split()[-1]
        code, out = run(capsys, "accesskey", "list", "A")
        assert key in out and "view, buy" in out
        code, out = run(capsys, "accesskey", "delete", key)
        assert code == 0

    def test_status_and_version(self, storage_env, capsys):
        code, out = run(capsys, "status")
        assert code == 0
        assert "ready to go" in out
        code, out = run(capsys, "version")
        assert code == 0

    def test_status_names_the_device_it_found(self, storage_env, capsys):
        code, out = run(capsys, "status")
        assert code == 0
        assert "Device: cpu x8 (cpu)" in out

    def test_status_fails_when_the_platform_does_not_come_up(
        self, storage_env, capsys, monkeypatch
    ):
        """No "falls back to CPU": a platform that is not there is named
        and the diagnostic returns non-zero."""
        monkeypatch.setenv("PIO_PLATFORM", "nochip")
        code, out = run(capsys, "status")
        assert code == 1
        assert "Device: NOT AVAILABLE" in out and "nochip" in out
        assert "falls back" not in out and "fall back" not in out


class TestNoChipNoNumber:
    def test_train_with_an_unavailable_platform_fails_and_names_it(
        self, storage_env, tmp_path
    ):
        import json
        import subprocess
        import sys

        from predictionio_tpu.data import DataMap, Event
        from predictionio_tpu.data.storage.base import App

        app_id = storage_env.get_meta_data_apps().insert(App(name="MyApp"))
        le = storage_env.get_l_events()
        le.init_channel(app_id)
        le.batch_insert(
            [Event(event="rate", entity_type="user", entity_id=f"u{k % 7}",
                   target_entity_type="item", target_entity_id=f"i{k % 5}",
                   properties=DataMap({"rating": float(1 + k % 5)}))
             for k in range(40)],
            app_id=app_id,
        )
        with open(os.path.join(_REPO_ROOT, "examples", "recommendation", "engine.json")) as f:
            (tmp_path / "engine.json").write_text(json.dumps(json.load(f)))
        proc = subprocess.run(
            [sys.executable, "-m", "predictionio_tpu.tools.cli", "train",
             "--engine-dir", str(tmp_path)],
            env={**os.environ, "PIO_PLATFORM": "nochip", "PYTHONPATH": _REPO_ROOT},
            capture_output=True, text=True, timeout=180, cwd=str(tmp_path),
        )
        assert proc.returncode != 0
        assert "'nochip' (from PIO_PLATFORM) did not initialise" in proc.stderr
        assert "Training completed" not in proc.stdout

    def test_bench_without_a_chip_prints_no_number_and_exits_non_zero(self, tmp_path):
        import json
        import subprocess
        import sys

        env = {**os.environ, "PIO_BENCH_DEADLINE_S": "120"}
        env.pop("PIO_BENCH_PLATFORM", None)
        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO_ROOT, "bench.py")],
            env=env, capture_output=True, text=True, timeout=300, cwd=str(tmp_path),
        )
        assert proc.returncode != 0
        last = json.loads(proc.stdout.strip().splitlines()[-1])
        assert last["ok"] is False and "no accelerator" in last["error"]
        assert "value" not in last and "metric" not in last


class TestBuildVerbs:
    def test_template_list_and_get(self, storage_env, tmp_path, capsys):
        code, out = run(capsys, "template", "list")
        assert code == 0
        assert "recommendation" in out and "ncf" in out

        dst = tmp_path / "my-engine"
        code, out = run(
            capsys, "template", "get", "recommendation", str(dst),
            "--app-name", "Shop",
        )
        assert code == 0
        assert (dst / "engine.json").exists()
        import json

        variant = json.loads((dst / "engine.json").read_text())
        assert variant["datasource"]["params"]["appName"] == "Shop"

        # refuse to clobber a non-empty destination
        code, out = run(capsys, "template", "get", "recommendation", str(dst))
        assert code == 1

        code, out = run(capsys, "template", "get", "nope", str(tmp_path / "x"))
        assert code == 1

    def test_build_validates_engine_dir(self, storage_env, tmp_path, capsys):
        dst = tmp_path / "engine"
        run(capsys, "template", "get", "classification", str(dst))
        code, out = run(capsys, "build", "--engine-dir", str(dst), "--verbose")
        assert code == 0
        assert "Build finished" in out

        (dst / "engine.json").write_text('{"engineFactory": "no.such.module"}')
        code, out = run(capsys, "build", "--engine-dir", str(dst))
        assert code == 1
        assert "Error" in out

    def test_build_template_json_version_gate(self, storage_env, tmp_path, capsys):
        import json

        dst = tmp_path / "engine"
        run(capsys, "template", "get", "recommendation", str(dst))
        (dst / "template.json").write_text(
            json.dumps({"pio": {"version": {"min": "999.0.0"}}})
        )
        code, out = run(capsys, "build", "--engine-dir", str(dst))
        assert code == 0  # warn, do not fail (reference behavior: warning)
        assert "Warning" in out and "999.0.0" in out

    def test_run_script(self, storage_env, tmp_path, capsys):
        script = tmp_path / "main.py"
        script.write_text(
            "import sys\n"
            "import predictionio_tpu\n"
            "print('ran with', sys.argv[1])\n"
        )
        code, out = run(capsys, "run", "--engine-dir", str(tmp_path), str(script),
                        "hello")
        assert code == 0
        assert "ran with hello" in out

    def test_run_forwards_option_style_args(self, storage_env, tmp_path, capsys):
        script = tmp_path / "main.py"
        script.write_text("import sys\nprint('argv:', sys.argv[1:])\n")
        code, out = run(capsys, "run", "--engine-dir", str(tmp_path), str(script),
                        "--epochs", "5")
        assert code == 0
        assert "argv: ['--epochs', '5']" in out

    def test_template_get_refuses_file_destination(self, storage_env, tmp_path, capsys):
        target = tmp_path / "notes.txt"
        target.write_text("keep me")
        code, out = run(capsys, "template", "get", "recommendation", str(target))
        assert code == 1
        assert "exists" in out
        assert target.read_text() == "keep me"


class TestImportExport:
    def _seed(self, capsys, tmp_path, n=120):
        import datetime as dt
        import json

        run(capsys, "app", "new", "IO")
        src = tmp_path / "events.jsonl"
        base = dt.datetime(2022, 5, 1, tzinfo=dt.timezone.utc)
        with open(src, "w") as f:
            for i in range(n):
                f.write(json.dumps({
                    "event": "buy" if i % 3 else "$set",
                    "entityType": "user", "entityId": f"u{i % 7}",
                    **({"targetEntityType": "item", "targetEntityId": f"i{i % 5}"}
                       if i % 3 else {"properties": {"vip": True}}),
                    "eventTime": (base + dt.timedelta(minutes=i)).isoformat(),
                }) + "\n")
        code, out = run(capsys, "import", "--appid", "1", "--input", str(src))
        assert code == 0 and f"Imported {n} events" in out
        return n

    def test_json_round_trip(self, storage_env, tmp_path, capsys):
        import json

        n = self._seed(capsys, tmp_path)
        out_path = tmp_path / "out.jsonl"
        code, out = run(capsys, "export", "--appid", "1", "--output", str(out_path))
        assert code == 0 and f"Exported {n} events" in out
        rows = [json.loads(l) for l in out_path.read_text().splitlines()]
        assert len(rows) == n
        assert all("event" in r and "entityId" in r for r in rows)

    def test_parquet_round_trip(self, storage_env, tmp_path, capsys):
        """export --format parquet -> import reads it back (reference
        EventsToFile json/parquet parity, SURVEY 2.4 #30)."""
        n = self._seed(capsys, tmp_path)
        pq = tmp_path / "out.parquet"
        code, out = run(capsys, "export", "--appid", "1",
                        "--output", str(pq), "--format", "parquet")
        assert code == 0 and f"Exported {n} events" in out

        # import the parquet into a second app; full fidelity round trip
        run(capsys, "app", "new", "IO2")
        code, out = run(capsys, "import", "--appid", "2", "--input", str(pq))
        assert code == 0 and f"Imported {n} events" in out

        from predictionio_tpu.data import storage as reg

        a = sorted(
            (e.event, e.entity_id, e.target_entity_id, e.event_time,
             e.properties.to_dict())
            for e in reg.get_l_events().find(1)
        )
        b = sorted(
            (e.event, e.entity_id, e.target_entity_id, e.event_time,
             e.properties.to_dict())
            for e in reg.get_l_events().find(2)
        )
        assert a == b

    def test_bad_rows_are_rejected_not_fatal(self, storage_env, tmp_path, capsys):
        import json

        run(capsys, "app", "new", "IO")
        src = tmp_path / "events.jsonl"
        with open(src, "w") as f:
            f.write(json.dumps({"event": "buy", "entityType": "user",
                                "entityId": "u1"}) + "\n")
            f.write("{not json\n")
            f.write(json.dumps({"event": "pio_reserved", "entityType": "user",
                                "entityId": "u2"}) + "\n")
        code, out = run(capsys, "import", "--appid", "1", "--input", str(src))
        assert code == 1  # errors reported
        assert "Imported 1 events (2 rejected)" in out
