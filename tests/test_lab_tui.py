"""Lab TUI shell: key handling, three-pane rendering, refresh, launch cards."""

import json

import pytest

from prime_tpu.core.client import APIClient
from prime_tpu.core.config import Config
from prime_tpu.lab.data import LabDataSource
from prime_tpu.lab.tui import PrimeLabApp, render_text
from prime_tpu.lab.tui.app import SECTIONS
from prime_tpu.lab.tui.keys import decode_key
from prime_tpu.testing import FakeControlPlane


@pytest.fixture
def fake():
    return FakeControlPlane()


@pytest.fixture
def api(fake):
    cfg = Config()
    cfg.api_key = "test-key"
    return APIClient(config=cfg, base_url="https://api.fake", transport=fake.transport)


@pytest.fixture
def app(fake, api, tmp_path):
    source = LabDataSource(tmp_path, api_client=api)
    return PrimeLabApp(data_source=source, workspace=tmp_path, api_client=api)


def _local_run(tmp_path, env="gsm8k", model="m1", run="r1", accuracy=0.5, n_samples=None):
    """One local eval run dir; n_samples also writes a results.jsonl."""
    run_dir = tmp_path / "outputs" / "evals" / f"{env}--{model}" / run
    run_dir.mkdir(parents=True)
    (run_dir / "metadata.json").write_text(
        json.dumps({"metrics": {"accuracy": accuracy, "num_samples": n_samples or 4}})
    )
    if n_samples:
        with open(run_dir / "results.jsonl", "w") as f:
            for i in range(n_samples):
                f.write(
                    json.dumps(
                        {"prompt": f"p{i}", "completion": "c", "reward": float(accuracy), "correct": True}
                    )
                    + "\n"
                )
    return run_dir


# -- key decoding -------------------------------------------------------------


@pytest.mark.parametrize(
    "data,key",
    [
        (b"\r", "enter"),
        (b"\t", "tab"),
        (b"q", "q"),
        (b"\x1b[A", "up"),
        (b"\x1b[B", "down"),
        (b"\x1b", "escape"),
        (b"\x03", "ctrl+c"),
        (b"\x1b[Z", None),  # unbound sequence ignored
        (b"\x00", None),
    ],
)
def test_decode_key(data, key):
    assert decode_key(data) == key


# -- navigation ---------------------------------------------------------------


def test_nav_cycles_sections_and_digit_jump(app):
    assert app.section == SECTIONS[0]
    app.on_key("down")
    assert app.section == SECTIONS[1]
    app.on_key("up")
    app.on_key("up")
    assert app.section == SECTIONS[-1]  # wraps
    app.on_key("3")
    assert app.section == SECTIONS[2] and app.focus == "rows"


def test_cursor_clamps_to_rows(app, tmp_path):
    _local_run(tmp_path, run="r1")
    _local_run(tmp_path, run="r2")
    app.tick()
    app.focus = "rows"
    app.on_key("down")
    app.on_key("down")
    app.on_key("down")
    assert app.cursors["local-runs"] == 1  # clamped to 2 rows
    app.on_key("g")
    assert app.cursors["local-runs"] == 0
    app.on_key("G")
    assert app.cursors["local-runs"] == 1


def test_quit_key(app):
    app.on_key("q")
    assert app.quit


# -- rendering ----------------------------------------------------------------


def test_render_three_panes_headless(app, tmp_path):
    _local_run(tmp_path, env="arith", model="tiny", run="r9", accuracy=1.0)
    app.tick()
    text = render_text(app)
    assert "PRIME LAB" in text
    assert "sections" in text and "inspector" in text
    assert "Local eval runs" in text
    assert "arith" in text and "r9" in text
    # nav shows counts for every section
    assert "Launch cards (0)" in text


def test_render_empty_section(app):
    app.on_key("7")  # sandboxes (no fetch yet)
    text = render_text(app)
    assert "(empty)" in text


def test_inspector_shows_selected_row(app, tmp_path):
    _local_run(tmp_path, env="arith", model="tiny", run="zzz", accuracy=0.25)
    app.tick()
    app.focus = "rows"
    text = render_text(app)
    assert "zzz" in text
    assert "0.250" in text  # float formatting in inspector/table


# -- refresh ------------------------------------------------------------------


def test_refresh_all_hydrates_platform_sections(app, fake, api):
    # create a sandbox through the SDK so the platform has a row
    from prime_tpu.sandboxes import SandboxClient
    from prime_tpu.sandboxes.models import CreateSandboxRequest

    SandboxClient(client=api).create(CreateSandboxRequest())
    app.on_key("R")
    assert app.status == "refreshed"
    rows = app.snapshot.platform["sandboxes"]
    assert len(rows) == 1
    app.on_key("7")
    text = render_text(app)
    assert rows[0]["sandboxId"][:12] in text


def test_refresh_errors_reported_in_status(app, monkeypatch):
    def boom():
        raise RuntimeError("plane down")

    monkeypatch.setattr(app.data, "_fetch_pods", boom)
    app.on_key("6")  # pods
    app.on_key("r")
    assert "pods: plane down" in app.status


# -- launch cards -------------------------------------------------------------


def _write_card(tmp_path, name="card1", kind="eval"):
    launch = tmp_path / ".prime-lab" / "launch"
    launch.mkdir(parents=True, exist_ok=True)
    (launch / f"{name}.toml").write_text(
        f'[launch]\nkind = "{kind}"\nname = "{name}"\n\n'
        f"[{kind}]\n"
        + ('env = "arith"\nmodel = "tiny-test"\n' if kind == "eval" else 'model = "llama3-8b"\nenvId = "env_x"\n')
    )


def test_launch_section_lists_cards(app, tmp_path):
    _write_card(tmp_path, "nightly", "eval")
    app.on_key("8")  # launch section
    text = render_text(app)
    assert "nightly" in text and "eval" in text


def test_launch_requires_arm_then_submits(app, tmp_path, fake):
    _write_card(tmp_path, "nightly", "eval")
    app.on_key("8")
    app.focus = "rows"
    app.on_key("enter")
    assert "press enter again" in app.status
    assert not fake.evals_plane.hosted
    app.on_key("enter")
    assert "launched eval heval_" in app.status
    assert len(fake.evals_plane.hosted) == 1


def test_launch_disarms_on_move_or_escape(app, tmp_path, fake):
    _write_card(tmp_path, "a-card", "eval")
    _write_card(tmp_path, "b-card", "eval")
    app.on_key("8")
    app.focus = "rows"
    app.on_key("enter")
    app.on_key("down")  # moving disarms
    app.on_key("enter")
    assert "press enter again" in app.status
    app.on_key("escape")
    assert "disarmed" in app.status
    assert not fake.evals_plane.hosted


def test_malformed_card_ignored(app, tmp_path):
    launch = tmp_path / ".prime-lab" / "launch"
    launch.mkdir(parents=True)
    (launch / "broken.toml").write_text("not [ valid toml")
    (launch / "wrongkind.toml").write_text('[launch]\nkind = "dance"\n')
    app.on_key("8")
    assert app.rows() == []


# -- CLI entry ----------------------------------------------------------------


def test_lab_tui_requires_tty(fake, monkeypatch):
    from click.testing import CliRunner

    import prime_tpu.commands._deps as deps
    from prime_tpu.commands.main import cli

    monkeypatch.setattr(deps, "transport_override", fake.transport)
    monkeypatch.setenv("PRIME_API_KEY", "test-key")
    monkeypatch.setenv("PRIME_BASE_URL", "https://api.fake")
    result = CliRunner().invoke(cli, ["lab", "tui"])
    assert result.exit_code != 0
    assert "interactive terminal" in result.output


def test_decode_keys_batched():
    from prime_tpu.lab.tui.keys import decode_keys

    assert decode_keys(b"jjj") == ["j", "j", "j"]
    assert decode_keys(b"\x1b[A\x1b[A") == ["up", "up"]
    assert decode_keys(b"j\x1b[Bq") == ["j", "down", "q"]
    assert decode_keys(b"\x1b[Zjq") == ["j", "q"]  # unknown CSI swallowed
    assert decode_keys(b"\x1bq") == ["escape", "q"]


def test_view_explicit_bad_target_errors(fake, monkeypatch, tmp_path):
    from click.testing import CliRunner

    import prime_tpu.commands._deps as deps
    from prime_tpu.commands.main import cli

    monkeypatch.setattr(deps, "transport_override", fake.transport)
    monkeypatch.setenv("PRIME_API_KEY", "test-key")
    monkeypatch.setenv("PRIME_BASE_URL", "https://api.fake")
    result = CliRunner().invoke(cli, ["eval", "view", str(tmp_path / "nope-typo")])
    assert result.exit_code != 0
    assert "not a run directory" in result.output


# -- training charts (reference training_charts.py role) ----------------------


def _training_run(tmp_path, name="run1", steps=20):
    import math

    run = tmp_path / "outputs" / "train" / name
    run.mkdir(parents=True)
    with open(run / "metrics.jsonl", "w") as f:
        for step in range(steps):
            f.write(json.dumps({
                "step": step,
                "loss": 5.0 * math.exp(-step / 7) + 1.0,
                "grad_norm": 2.0,
                "tokens_per_sec": 1000.0 + step,
                "step_time_s": 0.1,
            }) + "\n")


def test_sparkline_shapes():
    from prime_tpu.lab.tui.charts import sparkline

    assert sparkline([]) == ""
    assert sparkline([1.0, 1.0, 1.0]) == "▁▁▁"
    line = sparkline([0, 1, 2, 3, 4, 5, 6, 7])
    assert line[0] == "▁" and line[-1] == "█" and len(line) == 8
    assert len(sparkline(list(range(1000)), width=40)) == 40


def test_local_training_section_and_chart(app, tmp_path):
    _training_run(tmp_path, "sweep-a")
    app.tick()
    app.on_key("2")  # local-training section
    rows = app.rows()
    assert rows and rows[0]["run"] == "sweep-a" and rows[0]["steps"] == 19
    text = render_text(app)
    assert "Local training" in text and "sweep-a" in text
    assert "loss" in text and "▁" in text  # sparkline rendered in inspector
    assert "tokens_per_sec" in text


def test_training_chart_lines_skip_missing_metrics():
    from prime_tpu.lab.tui.charts import training_chart_lines

    rows = [{"step": i, "loss": 3.0 - i * 0.1} for i in range(10)]
    lines = training_chart_lines(rows)
    assert len(lines) == 1 and lines[0].strip().startswith("loss")


def test_metrics_scan_survives_partial_tail_line(app, tmp_path):
    _training_run(tmp_path, "mid-write", steps=5)
    path = tmp_path / "outputs" / "train" / "mid-write" / "metrics.jsonl"
    with open(path, "a") as f:
        f.write('{"step": 5, "loss": 1.')  # torn append
    app.tick()
    app.on_key("2")
    rows = app.rows()
    assert rows and rows[0]["steps"] == 4  # parsed rows kept, tail skipped


def test_metrics_scan_caches_on_mtime(app, tmp_path, monkeypatch):
    _training_run(tmp_path, "cached", steps=5)
    app.tick()
    calls = {"n": 0}
    original = json.loads

    def counting_loads(*a, **k):
        calls["n"] += 1
        return original(*a, **k)

    monkeypatch.setattr(json, "loads", counting_loads)
    import prime_tpu.lab.data as data_mod

    monkeypatch.setattr(data_mod.json, "loads", counting_loads)
    app.tick()  # unchanged file: no re-parse
    assert calls["n"] == 0


def test_sparkline_last_bucket_includes_newest_sample():
    from prime_tpu.lab.tui.charts import sparkline

    # huge final spike must show in the last cell even with inexact buckets
    values = [0.0] * 999 + [100.0]
    assert sparkline(values, width=48)[-1] != "▁"


def test_block_chart_levels_and_shape():
    from prime_tpu.lab.tui.charts import block_chart

    rows = block_chart([0.0, 0.5, 1.0], width=10, height=4)
    assert len(rows) == 4 and all(len(r) == 3 for r in rows)
    # max column fills the top row; min column only the bottom's smallest block
    assert rows[0][2] == "█" and rows[0][0] == " "
    assert rows[3][0] == "▁"
    # constant series renders mid-height, not empty
    flat = block_chart([2.0, 2.0, 2.0], width=10, height=4)
    assert any(ch != " " for r in flat for ch in r)


def test_ema_and_adaptive_retention():
    from prime_tpu.lab.tui.charts import adaptive_retention, ema

    assert ema([], 0.9) == []
    assert ema([1.0, 1.0, 1.0], 0.9) == [1.0, 1.0, 1.0]
    smoothed = ema([0.0, 10.0], 0.5)
    assert smoothed == [0.0, 5.0]
    assert adaptive_retention(4) == 0.0          # short series stay raw
    assert 0.9 < adaptive_retention(1000) <= 0.98


def test_chart_panel_labels_window_and_smooth():
    from prime_tpu.lab.tui.charts import chart_panel

    rows = [{"step": i, "loss": 10.0 - i * 0.01} for i in range(600)]
    panel = chart_panel(rows, "loss", width=40, height=4)
    assert panel[0][0] == "bold" and "last=4.01" in panel[0][1]
    assert "step 0 → 599" in panel[-1][1]
    # window keeps only the tail
    windowed = chart_panel(rows, "loss", width=40, height=4, window=128)
    assert "step 472 → 599 (128 pts)" in windowed[-1][1]
    # smoothing tags the title but stats stay raw
    smooth = chart_panel(rows, "loss", width=40, height=4, smooth=True)
    assert "(ema)" in smooth[0][1] and "last=4.01" in smooth[0][1]
    # missing metric or too-few points -> empty
    assert chart_panel(rows, "absent") == []
    assert chart_panel(rows[:1], "loss") == []


def test_chart_panel_gutter_matches_bucketed_columns():
    from prime_tpu.lab.tui.charts import chart_panel

    # one 9.0 outlier in ~1.0 noise, 600 pts into 40 buckets: the outlier's
    # bucket mean is ~1.5, so the axis label must NOT claim the chart shows 9
    rows = [{"step": i, "reward": 9.0 if i == 300 else 1.0} for i in range(600)]
    panel = chart_panel(rows, "reward", width=40, height=4)
    top_label = panel[1][1].split()[0]
    assert float(top_label) < 2.0
    assert "max=9" in panel[0][1]  # the stats line still reports the raw max


def test_discover_metrics_order_and_exclusions():
    from prime_tpu.lab.tui.charts import discover_metrics

    rows = [
        {"step": 1, "ts": 123.0, "tokens_per_sec": 900.0, "loss": 2.0, "reward_mean": 0.5},
        {"step": 2, "flag": True, "note": "text", "grad_norm": 1.0},
    ]
    keys = discover_metrics(rows)
    assert keys[0] in ("loss", "reward_mean") and keys[1] in ("loss", "reward_mean")
    assert "step" not in keys and "ts" not in keys
    assert "flag" not in keys and "note" not in keys
    assert "tokens_per_sec" in keys and "grad_norm" in keys


def test_training_detail_block_chart_smooth_and_window(app, tmp_path):
    run_dir = tmp_path / "outputs" / "train" / "runZ"
    run_dir.mkdir(parents=True)
    with open(run_dir / "metrics.jsonl", "w") as f:
        for step in range(200):
            f.write(json.dumps({"step": step, "loss": 5.0 - step * 0.01}) + "\n")
    app.tick()
    app.on_key("2")
    app.on_key("enter")
    detail = app.screens[-1]
    text = render_text(app)
    assert "last=3.01" in text and "step 0 → 199" in text
    app.on_key("s")
    assert detail.smooth and "(ema)" in render_text(app)
    app.on_key("]")          # zoom in one step on the window ladder
    assert "last 512" in app.status or "last 128" in app.status
    app.on_key("[")
    assert detail.window_idx == 0
    app.on_key("escape")


def test_eval_tui_requires_tty(fake, monkeypatch):
    from click.testing import CliRunner

    import prime_tpu.commands._deps as deps
    from prime_tpu.commands.main import cli

    monkeypatch.setattr(deps, "transport_override", fake.transport)
    monkeypatch.setenv("PRIME_API_KEY", "test-key")
    monkeypatch.setenv("PRIME_BASE_URL", "https://api.fake")
    result = CliRunner().invoke(cli, ["eval", "tui"])
    assert result.exit_code != 0
    assert "interactive terminal" in result.output


# -- detail screens (VERDICT r2 #3: section -> row -> detail -> back) ---------


def _run_with_samples(tmp_path, n=4):
    run_dir = _local_run(tmp_path)
    with open(run_dir / "results.jsonl", "w") as f:
        for i in range(n):
            f.write(
                json.dumps(
                    {
                        "prompt": f"what is {i}+{i}?",
                        "completion": str(2 * i),
                        "answer": str(2 * i) if i % 2 == 0 else "nope",
                        "reward": 1.0 if i % 2 == 0 else 0.0,
                        "correct": i % 2 == 0,
                    }
                )
                + "\n"
            )
    return run_dir


def test_eval_detail_drilldown_and_back(app, tmp_path):
    _run_with_samples(tmp_path)
    app.tick()
    app.on_key("1")          # local-runs section, rows focus
    app.on_key("enter")      # drill into the run overview
    assert app.screens and "eval:" in app.screens[-1].title
    text = render_text(app)
    assert "pass rate" in text and "50.0%" in text and "reward dist" in text
    app.on_key("enter")      # overview -> sample browser
    text = render_text(app)
    assert "sample 1/4" in text and "what is 0+0?" in text
    app.on_key("n")          # next sample
    assert "sample 2/4" in render_text(app)
    app.on_key("escape")     # back to the overview
    assert app.screens and app.screens[-1].__class__.__name__ == "EvalRunOverview"
    app.on_key("escape")     # back to the shell
    assert not app.screens
    assert "Local eval runs" in render_text(app)


def test_eval_detail_filter_and_search(app, tmp_path):
    _run_with_samples(tmp_path)
    app.tick()
    app.on_key("1")
    app.on_key("enter")      # overview
    app.on_key("enter")      # sample browser
    browser = app.screens[-1]
    app.on_key("f")          # all -> correct
    assert browser.filter_mode == "correct" and len(browser.visible()) == 2
    app.on_key("f")          # correct -> incorrect
    assert browser.filter_mode == "incorrect"
    assert all(not browser.samples[i]["correct"] for i in browser.visible())
    app.on_key("f")          # back to all
    for ch in ("/", "3", "+", "3"):
        app.on_key(ch)
    app.on_key("enter")      # jump to the sample containing "3+3"
    assert browser.samples[browser.idx]["prompt"] == "what is 3+3?"
    # 'q' during search input types a literal q instead of quitting
    app.on_key("/")
    app.on_key("q")
    assert not app.quit and browser.search_input == "q"
    app.on_key("escape")     # cancel search input
    assert browser.search_input is None and app.screens


def test_eval_overview_reload_sees_appended_rows(app, tmp_path):
    run_dir = _run_with_samples(tmp_path)
    app.tick()
    app.on_key("1")
    app.on_key("enter")      # overview
    overview = app.screens[-1]
    assert overview.overview.n_samples == 4
    with open(run_dir / "results.jsonl", "a") as f:
        f.write(json.dumps({"prompt": "late", "completion": "x", "reward": 1.0, "correct": True}) + "\n")
    app.on_key("r")
    assert overview.overview.n_samples == 5
    assert "5 samples" in app.status


def test_sample_browser_markdown_toggle(app, tmp_path):
    run_dir = _local_run(tmp_path)
    with open(run_dir / "results.jsonl", "w") as f:
        f.write(
            json.dumps(
                {
                    "prompt": r"compute $\frac{1}{2}$ of **eight**",
                    "completion": "```python\nprint(4)\n```",
                    "answer": "4",
                    "reward": 1.0,
                    "correct": True,
                }
            )
            + "\n"
        )
    app.tick()
    app.on_key("1")
    app.on_key("enter")      # overview
    app.on_key("enter")      # browser
    text = render_text(app)
    assert r"$\frac{1}{2}$" in text          # raw by default
    app.on_key("m")
    text = render_text(app)
    assert "(1)/(2) of eight" in text         # math + bold rendered
    assert "print(4)" in text
    app.on_key("m")
    assert r"$\frac{1}{2}$" in render_text(app)


def test_sample_browser_chat_messages_sections(app, tmp_path):
    """Multi-turn rollouts (a `messages` list) render one section per role
    turn, including part-list content; search spans the turns."""
    run_dir = _local_run(tmp_path)
    with open(run_dir / "results.jsonl", "w") as f:
        f.write(
            json.dumps(
                {
                    "messages": [
                        {"role": "system", "content": "be terse"},
                        {"role": "user", "content": [{"type": "text", "text": "what is 2+2?"}]},
                        {"role": "assistant", "content": "4"},
                    ],
                    "answer": "4",
                    "reward": 1.0,
                    "correct": True,
                }
            )
            + "\n"
        )
        f.write(json.dumps({"prompt": "plain row", "completion": "x", "reward": 0.0, "correct": False}) + "\n")
    app.tick()
    app.on_key("1")
    app.on_key("enter")      # overview
    app.on_key("enter")      # browser
    text = render_text(app)
    assert "SYSTEM" in text and "USER" in text and "ASSISTANT" in text
    assert "what is 2+2?" in text            # part-list content flattened
    assert "PROMPT" not in text              # chat rows don't show the flat labels
    # search reaches message turns and jumps across row shapes
    for ch in "/plain":
        app.on_key(ch)
    app.on_key("enter")
    assert "match at sample 2/2" in app.status
    assert "plain row" in render_text(app) and "PROMPT" in render_text(app)


def test_training_detail_tabs_and_reload(app, tmp_path):
    run_dir = tmp_path / "outputs" / "train" / "run1"
    run_dir.mkdir(parents=True)
    with open(run_dir / "metrics.jsonl", "w") as f:
        for step in range(6):
            f.write(json.dumps({"step": step, "loss": 3.0 - step * 0.3, "tokens_per_sec": 900.0 + step}) + "\n")
    (run_dir / "config.json").write_text(json.dumps({"model": "tiny-test", "lr": 3e-4}))
    (run_dir / "train.log").write_text("line-a\nline-b\n")
    app.tick()
    app.on_key("2")          # local-training
    app.on_key("enter")
    assert app.screens and "training:" in app.screens[-1].title
    text = render_text(app)
    assert "loss" in text   # chart tab renders metric sparkline
    app.on_key("tab")        # -> config
    text = render_text(app)
    assert "tiny-test" in text and "lr" in text
    app.on_key("tab")        # -> logs
    text = render_text(app)
    assert "line-a" in text and "line-b" in text
    app.on_key("r")          # reload does not crash and keeps metrics
    assert app.screens[-1].metrics
    app.on_key("escape")
    assert not app.screens


def test_hub_eval_detail_fetches_samples(app, fake, api):
    from prime_tpu.evals import EvalsClient
    from prime_tpu.evals.models import CreateEvaluationRequest

    client = EvalsClient(api)
    ev = client.create_evaluation(CreateEvaluationRequest(env="gsm8k", model="m1"))
    client.push_samples(
        ev.eval_id,
        [
            {"sample_id": "s0", "prompt": "p0", "completion": "c0", "reward": 1.0, "correct": True},
            {"sample_id": "s1", "prompt": "p1", "completion": "c1", "reward": 0.0, "correct": False},
        ],
    )
    app.refresh_all()
    app.on_key("3")          # evals hub section
    app.on_key("enter")
    assert app.screens
    text = render_text(app)
    assert "sample 1/2" in text and "p0" in text


def test_env_detail_versions_and_actions(app, fake, api, tmp_path):
    fake.envhub_plane.environments["arith"] = {
        "name": "arith",
        "versions": ["0.1.0", "0.2.0"],
        "owner": "dev",
        "visibility": "private",
    }
    fake.envhub_plane.actions["arith"] = [
        {"id": "act_1", "kind": "build", "status": "completed", "logs": ["built ok"]}
    ]
    app.refresh_all()
    app.on_key("5")          # environments
    app.on_key("enter")
    assert app.screens and app.screens[-1].title == "env: arith"
    text = render_text(app)
    assert "0.2.0" in text and "act_1" in text
    app.on_key("enter")      # fetch logs for the selected action
    text = render_text(app)
    assert "built ok" in text
    app.on_key("escape")
    assert not app.screens


# -- config-card editor (reference config_screen.py role) ---------------------


def test_card_editor_edit_save_roundtrip(app, tmp_path):
    import tomllib

    _write_card(tmp_path, "sweep", "eval")
    app.on_key("8")              # launch section
    app.on_key("e")              # open editor
    assert app.screens and app.screens[-1].title == "edit: sweep.toml"
    editor = app.screens[-1]
    # move to the "model" field and retype its value
    while editor.fields[editor.cursor][0] != "model":
        app.on_key("j")
    app.on_key("enter")          # edit mode, prefilled with current value
    for _ in range("tiny-test".__len__()):
        app.on_key("backspace")
    for ch in "llama3-8b":
        app.on_key(ch)
    app.on_key("enter")          # commit
    assert editor.dirty
    app.on_key("s")              # save
    assert not editor.dirty
    data = tomllib.loads((tmp_path / ".prime-lab" / "launch" / "sweep.toml").read_text())
    assert data["eval"]["model"] == "llama3-8b"
    assert data["launch"]["kind"] == "eval"
    app.on_key("escape")
    assert not app.screens
    # the shell's launch row reflects the rescan
    assert "sweep" in render_text(app)


def test_card_editor_add_delete_and_typing(app, tmp_path):
    import tomllib

    _write_card(tmp_path, "card2", "eval")
    app.on_key("8")
    app.on_key("e")
    editor = app.screens[-1]
    app.on_key("a")              # add field
    for ch in "num_samples=64":
        app.on_key(ch)
    app.on_key("enter")
    app.on_key("a")
    for ch in "push=false":
        app.on_key(ch)
    app.on_key("enter")
    app.on_key("s")
    data = tomllib.loads(editor.card.path.read_text())
    assert data["eval"]["num_samples"] == 64          # typed int, not "64"
    assert data["eval"]["push"] is False              # typed bool
    # delete it again (cursor sits on the later-added "push"; num_samples is above)
    while editor.fields[editor.cursor][0] != "num_samples":
        app.on_key("k")
    app.on_key("d")
    app.on_key("s")
    data = tomllib.loads(editor.card.path.read_text())
    assert "num_samples" not in data["eval"]


def test_card_editor_new_card_and_launch(app, fake, tmp_path):
    app.on_key("8")
    app.on_key("n")              # new card template
    editor = app.screens[-1]
    assert editor.card.kind == "eval" and not editor.card.path.exists()
    app.on_key("L")                          # launch before save
    assert "unsaved" in app.status           # guard message surfaced via app
    assert "unsaved" in (editor.launch())    # and via the direct call
    editor.dirty = True
    app.on_key("s")
    assert editor.card.path.exists()
    app.on_key("L")              # launch through the fake plane
    assert "launched eval" in app.status or "launched eval" in editor.message


def test_card_editor_payload_name_key_survives(app, tmp_path):
    """A payload key literally named `name` must not collide with the
    [launch].name pseudo-field: zero-edit save keeps both intact."""
    import tomllib

    base = tmp_path / ".prime-lab" / "launch"
    base.mkdir(parents=True, exist_ok=True)
    (base / "named.toml").write_text(
        '[launch]\nkind = "eval"\nname = "outer"\n\n[eval]\nname = "inner"\nmodel = "m"\n'
    )
    app.on_key("8")
    while app.selected_row() and app.selected_row()["name"] != "outer":
        app.on_key("j")
    app.on_key("e")
    editor = app.screens[-1]
    editor.dirty = True
    app.on_key("s")
    data = tomllib.loads((base / "named.toml").read_text())
    assert data["launch"]["name"] == "outer"
    assert data["eval"]["name"] == "inner"


def test_card_editor_rejects_dotted_keys(app, tmp_path):
    _write_card(tmp_path, "card4", "eval")
    app.on_key("8")
    app.on_key("e")
    editor = app.screens[-1]
    app.on_key("a")
    for ch in "lr.schedule=cosine":
        app.on_key(ch)
    app.on_key("enter")
    assert "must be bare" in editor.message
    assert all(k != "lr.schedule" for k, _ in editor.fields)


# -- run comparison (eval compare, in-shell) ----------------------------------


def _run_with_flips(tmp_path, run, rewards):
    """Run dir whose sample i is correct iff rewards[i]; prompts shared."""
    run_dir = tmp_path / "outputs" / "evals" / "gsm8k--m1" / run
    run_dir.mkdir(parents=True)
    accuracy = sum(rewards) / len(rewards)
    (run_dir / "metadata.json").write_text(
        json.dumps({"metrics": {"accuracy": accuracy, "num_samples": len(rewards)}})
    )
    with open(run_dir / "results.jsonl", "w") as f:
        for i, ok in enumerate(rewards):
            f.write(
                json.dumps(
                    {
                        "prompt": f"q{i}",
                        "completion": f"{run}-ans{i}",
                        "answer": str(i),
                        "reward": 1.0 if ok else 0.0,
                        "correct": bool(ok),
                    }
                )
                + "\n"
            )
    return run_dir


def test_compare_runs_flips_and_metric_deltas(tmp_path):
    from prime_tpu.lab.evalrecords import compare_runs

    dir_a = _run_with_flips(tmp_path, "run-a", [1, 1, 0, 0])
    dir_b = _run_with_flips(tmp_path, "run-b", [1, 0, 1, 0])
    comparison = compare_runs(dir_a, dir_b)
    assert comparison.shared == 4
    assert comparison.regressions == 1 and comparison.improvements == 1
    directions = {f.key: f.direction for f in comparison.flips}
    assert directions == {"q1": "regression", "q2": "improvement"}
    accuracy = next(m for m in comparison.metrics if m[0] == "accuracy")
    assert accuracy[3] == pytest.approx(0.0)   # 0.5 -> 0.5


def test_compare_runs_edge_cases(tmp_path):
    """sample_id 0 keys, rows without 'correct', and duplicate keys."""
    from prime_tpu.lab.evalrecords import compare_runs

    def write(run, rows):
        run_dir = tmp_path / "outputs" / "evals" / "e--m" / run
        run_dir.mkdir(parents=True)
        (run_dir / "metadata.json").write_text(json.dumps({"metrics": {}}))
        with open(run_dir / "results.jsonl", "w") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
        return run_dir

    dir_a = write(
        "a",
        [
            {"sample_id": 0, "completion": "x", "correct": True},   # falsy key kept
            {"prompt": "dup", "completion": "first", "correct": True},
            {"prompt": "dup", "completion": "second", "correct": False},  # ignored
            {"prompt": "reward-only", "completion": "r", "reward": 0.5},  # no correct
        ],
    )
    dir_b = write(
        "b",
        [
            {"sample_id": 0, "completion": "y", "correct": False},
            {"prompt": "dup", "completion": "other", "correct": True},
            {"prompt": "reward-only", "completion": "r2", "correct": True},
        ],
    )
    comparison = compare_runs(dir_a, dir_b)
    assert comparison.shared == 3
    assert comparison.duplicates == 1
    # sample 0 regressed; dup compares first occurrences (no flip);
    # reward-only is excluded from flip accounting, not counted as regression
    assert [(f.key, f.direction) for f in comparison.flips] == [("0", "regression")]


def test_compare_screen_via_x_marks(app, tmp_path):
    _run_with_flips(tmp_path, "run-a", [1, 0])
    _run_with_flips(tmp_path, "run-b", [0, 1])
    app.tick()
    app.on_key("1")
    app.on_key("x")                  # mark baseline (first row)
    assert "baseline" in app.status
    app.on_key("j")
    app.on_key("x")                  # compare with second row
    assert app.screens and app.screens[-1].title.startswith("compare:")
    text = render_text(app)
    assert "improvements" in text and "regressions" in text
    app.on_key("enter")              # expand the selected flip
    text = render_text(app)
    assert "ans0" in text            # both completions shown
    app.on_key("f")                  # filter cycles
    assert "filter:" in app.status
    app.on_key("escape")
    assert not app.screens


def test_help_overlay(app):
    app.on_key("?")
    text = render_text(app)
    assert "Sample browser" in text and "markdown" in text.lower()
    app.on_key("escape")
    assert not app.screens


# -- grouped eval tree (reference evaluation_browser.py role) -----------------


def test_eval_tree_groups_and_aggregates(app, tmp_path):
    _local_run(tmp_path, "gsm8k", "m1", "run-a", 0.5, n_samples=2)
    _local_run(tmp_path, "gsm8k", "m1", "run-b", 1.0, n_samples=2)
    _local_run(tmp_path, "gsm8k", "m2", "run-c", 0.25, n_samples=2)
    _local_run(tmp_path, "math", "m1", "run-d", 0.75, n_samples=2)
    app.tick()
    app.on_key("1")
    app.on_key("t")
    tree = app.screens[-1]
    assert tree.title.startswith("eval runs")
    text = render_text(app)
    # env aggregates over all its models: gsm8k mean = (0.5+1.0+0.25)/3
    assert "gsm8k" in text and "3 run(s)" in text and "58.3%" in text
    assert "math" in text and "75.0%" in text
    # newest-first run ordering within a model
    assert text.index("run-b") < text.index("run-a")
    # collapse the gsm8k env: its models/runs disappear
    app.on_key("g")
    app.on_key(" ")
    text = render_text(app)
    assert "run-a" not in text and "m2" not in text and "math" in text
    app.on_key("enter")      # expand again (enter toggles groups too)
    assert "run-a" in render_text(app)


def test_eval_tree_opens_run_overview(app, tmp_path):
    _local_run(tmp_path, "gsm8k", "m1", "run-a", 1.0, n_samples=2)
    app.tick()
    app.on_key("1")
    app.on_key("t")
    tree = app.screens[-1]
    # walk down to the run node and open it
    while tree.current()["level"] != 2:
        app.on_key("j")
    app.on_key("enter")
    assert app.screens[-1].__class__.__name__ == "EvalRunOverview"
    assert "pass rate" in render_text(app)
    app.on_key("escape")     # back to the tree
    assert app.screens[-1] is tree
    app.on_key("escape")
    assert not app.screens


# -- agent config editor (reference agent_cards.py role) ----------------------


def test_agent_editor_create_and_edit(app, tmp_path):
    from prime_tpu.lab.tui.app import SECTIONS

    app.section_idx = SECTIONS.index("agents")
    app.focus = "rows"
    app.on_key("n")          # new agent
    editor = app.screens[-1]
    app.on_key("enter")      # edit name
    for _ in range(len("new-agent")):
        app.on_key("backspace")
    for ch in "helper":
        app.on_key(ch)
    app.on_key("enter")
    app.on_key("j")          # dialect row
    app.on_key("enter")      # cycle to the next dialect in the runtime table
    assert editor.entry["dialect"] == "codex"  # sorted table: acp -> codex
    app.on_key("s")
    assert "command is required" in app.status
    app.on_key("j")          # command row
    app.on_key("enter")
    for ch in "python -u agent.py":
        app.on_key(ch)
    app.on_key("enter")
    app.on_key("s")
    assert "saved helper" in app.status
    app.on_key("escape")
    # the agents section now lists it (load_agents_config reads the file)
    rows = app.rows("agents")
    assert any(r["name"] == "helper" and r["dialect"] == "codex" for r in rows)
    # re-open for edit and delete
    app.on_key("e")
    editor = app.screens[-1]
    assert editor.entry["name"] == "helper"
    app.on_key("d")
    assert not app.screens   # delete closes the editor
    assert all(r["name"] != "helper" for r in app.rows("agents"))


def test_agent_editor_resolves_nameless_row(app, tmp_path):
    """A row without a 'name' key is listed under its synthesized agent-<i>
    label; editing it must resolve to the row, not append a duplicate."""
    import json as _json

    cfg = tmp_path / ".prime-lab"
    cfg.mkdir(parents=True, exist_ok=True)
    (cfg / "agents.json").write_text(
        _json.dumps({"agents": [{"command": "python -u a.py", "dialect": "simple"}]})
    )
    from prime_tpu.lab.tui.app import SECTIONS

    app.section_idx = SECTIONS.index("agents")
    app.focus = "rows"
    rows = app.rows("agents")
    assert rows and rows[0]["name"] == "agent-0"
    app.on_key("e")
    editor = app.screens[-1]
    assert editor.entry["command"] == "python -u a.py"
    assert editor.entry["dialect"] == "simple"
    assert len(editor.agents) == 1   # no duplicate appended


# -- workspace setup screen (reference setup_screens.py role) -----------------


def test_setup_screen_runs_setup_and_doctor(app, tmp_path):
    app.on_key("S")
    screen = app.screens[-1]
    assert screen.title == "lab setup"
    # uncheck codex, keep claude
    while screen.surfaces[screen.cursor] != "codex":
        app.on_key("j")
    app.on_key(" ")
    assert not screen.checked["codex"] and screen.checked["claude"]
    app.on_key("enter")          # run setup
    assert "setup ok" in app.status
    assert (tmp_path / ".prime-lab" / "lab.toml").exists()
    assert (tmp_path / "CLAUDE.md").exists()
    assert not (tmp_path / "AGENTS.md").exists()
    text = render_text(app)
    assert "created" in text
    app.on_key("d")              # doctor pass
    assert "doctor" in app.status
    app.on_key("escape")
    assert not app.screens


def test_setup_screen_no_surfaces_checked(app):
    app.on_key("S")
    screen = app.screens[-1]
    for name in screen.surfaces:
        screen.checked[name] = False
    app.on_key("enter")
    assert "no surfaces checked" in app.status
    assert screen.report is None


def test_card_editor_q_types_not_quits(app, tmp_path):
    _write_card(tmp_path, "card3", "eval")
    app.on_key("8")
    app.on_key("e")
    editor = app.screens[-1]
    app.on_key("enter")          # edit mode
    app.on_key("q")
    assert not app.quit and editor.input.endswith("q")
    app.on_key("escape")         # cancel edit
    assert editor.input is None and app.screens


def test_sample_browser_tool_calls_reasoning_usage_state(app, tmp_path):
    """Round-4 render breadth: tool-call turns, tool replies paired by id,
    reasoning content, token usage, and env state all render (reference
    eval_render.py tool_call_parts / stringify_message_reasoning /
    build_usage_text / build_state_text roles)."""
    run_dir = _local_run(tmp_path)
    with open(run_dir / "results.jsonl", "w") as f:
        f.write(
            json.dumps(
                {
                    "messages": [
                        {"role": "user", "content": "weather in SF?"},
                        {
                            "role": "assistant",
                            "content": "",
                            "reasoning": "user wants current weather",
                            "tool_calls": [
                                {
                                    "id": "call_1",
                                    "function": {
                                        "name": "get_weather",
                                        "arguments": {"city": "SF"},
                                    },
                                }
                            ],
                        },
                        {"role": "tool", "tool_call_id": "call_1", "content": "64F sunny"},
                        {"role": "assistant", "content": "64F and sunny."},
                    ],
                    "usage": {"prompt_tokens": 21, "completion_tokens": 9},
                    "state": {"turns": 2},
                    "reward": 1.0,
                    "correct": True,
                }
            )
            + "\n"
        )
    app.tick()
    app.on_key("1")
    app.on_key("enter")
    app.on_key("enter")
    text = render_text(app)
    assert 'get_weather({"city": "SF"}) -> call_1' in text
    assert "TOOL get_weather (call_1)" in text and "64F sunny" in text
    assert "[reasoning] user wants current weather" in text
    assert "USAGE" in text and "completion_tokens=9" in text
    assert "STATE" in text and '"turns": 2' in text


def test_sample_browser_tool_chains_media_and_error_turns(app, tmp_path):
    """Round-5 render breadth (VERDICT r4 #3): multi-turn tool chains pair
    each reply with its calling tool by name across turns, image/file parts
    render as placeholders instead of vanishing, refusal/error turns and the
    sample-level harness error render explicitly."""
    run_dir = _local_run(tmp_path)
    with open(run_dir / "results.jsonl", "w") as f:
        f.write(
            json.dumps(
                {
                    "messages": [
                        {
                            "role": "user",
                            "content": [
                                {"type": "text", "text": "what is in this picture?"},
                                {"type": "image_url", "image_url": {"url": "https://x/cat.png"}},
                                {"type": "input_file", "filename": "notes.pdf"},
                            ],
                        },
                        {
                            "role": "assistant",
                            "content": "",
                            "tool_calls": [
                                {"id": "c1", "function": {"name": "look", "arguments": {}}},
                                {"id": "c2", "function": {"name": "fetch", "arguments": {}}},
                            ],
                        },
                        {"role": "tool", "tool_call_id": "c2", "content": "fetched"},
                        {"role": "tool", "tool_call_id": "c1", "content": "a cat"},
                        {"role": "tool", "tool_call_id": "c9", "content": "orphan reply"},
                        {
                            "role": "assistant",
                            "content": "",
                            "refusal": "I can't help with that.",
                            "error": "rate limited",
                        },
                    ],
                    "error": "rollout aborted after turn 6",
                    "reward": 0.0,
                    "correct": False,
                }
            )
            + "\n"
        )
    app.tick()
    app.on_key("1")
    app.on_key("enter")
    app.on_key("enter")
    text = render_text(app)
    # out-of-order replies still name their tools; orphans say so
    assert "TOOL fetch (c2)" in text and "TOOL look (c1)" in text
    assert "TOOL c9 (unmatched)" in text
    # media placeholders
    assert "[image: https://x/cat.png]" in text
    assert "[file: notes.pdf]" in text
    # refusal + per-turn error + sample-level error (scroll to the tail —
    # the ERROR/USAGE sections sit below the first page)
    assert "[refusal] I can't help with that." in text
    assert "[error] rate limited" in text
    for _ in range(30):
        app.on_key("j")
    tail = render_text(app)
    assert "ERROR" in tail and "rollout aborted after turn 6" in tail


def test_empty_text_parts_render_nothing(app, tmp_path):
    """An empty 'text' part (streamed turns that only carry tool_calls) must
    not leave a '[text]' placeholder behind."""
    from prime_tpu.lab.tui.detail import _content_text

    assert _content_text([{"type": "text", "text": ""}]) == ""
    assert _content_text([{"type": "reasoning"}]) == ""
    assert _content_text([{"type": "mystery_kind"}]) == "[mystery_kind]"
