"""Typed widget-payload model (VERDICT r3 missing #3): repair/reject agent
widget JSON, state round-trips, card lifecycle.

The contract under test: ANY payload fed through normalize_widget_call +
render_widget either renders (possibly repaired, with the repairs recorded)
or renders an explicit error panel — never a crash, never a silent
misrender. Reference role: prime_lab_app/agent_widget_model.py:1-1168,
agent_cards.py:1-536.
"""

import math

import pytest

from prime_tpu.lab.widget_model import (
    MAX_OPTIONS,
    MAX_PATCH_LINES,
    MAX_POINTS,
    MAX_ROWS,
    NormalizedWidget,
    WidgetValidationError,
    launch_card_payload,
    normalize_widget_call,
)
from prime_tpu.lab.widgets import render_widget


def _render_text(renderable) -> str:
    from rich.console import Console

    console = Console(width=100, record=True, file=None, force_terminal=False)
    console.print(renderable)
    return console.export_text()


# -- repair --------------------------------------------------------------------


def test_choose_repairs_scalars_nulls_dupes():
    widget = normalize_widget_call(
        "choose", {"options": ["a", None, 3, "a", "", "  b  "], "title": 7}
    )
    assert widget.args["options"] == ["a", "3", "b"]
    assert widget.args["title"] == "7"
    assert any("null" in r for r in widget.repairs)
    assert any("duplicate" in r for r in widget.repairs)
    text = _render_text(render_widget("choose", {"options": ["a", None, 3]}))
    assert "repaired" in text


def test_chart_coerces_numeric_strings_drops_junk():
    widget = normalize_widget_call(
        "show_chart", {"values": [1, "2.5", "x", None, float("nan"), float("inf"), True]}
    )
    assert widget.args["values"] == [1, 2.5]
    assert len(widget.repairs) == 6  # 5 drops + the '2.5' coercion note
    text = _render_text(render_widget("show_chart", {"values": ["1", "2", "3"]}))
    assert "repaired" in text


def test_table_drops_non_object_rows():
    widget = normalize_widget_call(
        "show_table", {"rows": [{"a": 1}, "junk", None, {"b": 2}]}
    )
    assert widget.args["rows"] == [{"a": 1}, {"b": 2}]
    assert len(widget.repairs) == 2


def test_launch_coerces_typed_config_fields():
    widget = normalize_widget_call(
        "launch_run",
        {
            "kind": "training",
            "config": {
                "model": "llama3-8b",
                "limit": "64",
                "temperature": "0.7",
                "batch_size": 8,
                "junk": {"nested": True},
                "hole": None,
            },
        },
    )
    config = widget.args["config"]
    assert config["limit"] == 64 and isinstance(config["limit"], int)
    assert config["temperature"] == 0.7 and isinstance(config["temperature"], float)
    assert config["batch_size"] == 8
    assert "junk" not in config and "hole" not in config


def test_patch_truncates_and_coerces():
    long_patch = "\n".join(f"+line {i}" for i in range(MAX_PATCH_LINES + 50))
    widget = normalize_widget_call("show_patch", {"patch": long_patch})
    assert len(widget.args["patch"].splitlines()) == MAX_PATCH_LINES
    assert any("truncated" in r for r in widget.repairs)


def test_size_caps():
    options = normalize_widget_call(
        "choose", {"options": [f"o{i}" for i in range(MAX_OPTIONS + 10)]}
    )
    assert len(options.args["options"]) == MAX_OPTIONS
    rows = normalize_widget_call(
        "show_table", {"rows": [{"i": i} for i in range(MAX_ROWS + 10)]}
    )
    assert len(rows.args["rows"]) == MAX_ROWS
    points = normalize_widget_call(
        "show_chart", {"values": list(range(MAX_POINTS * 3))}
    )
    assert len(points.args["values"]) == MAX_POINTS
    # downsampling keeps the series shape (monotone stays monotone)
    values = points.args["values"]
    assert values == sorted(values) and values[0] == 0


# -- reject --------------------------------------------------------------------


@pytest.mark.parametrize(
    "name,args,reason",
    [
        ("nope", {}, "unknown widget tool"),
        ("choose", "not-a-dict", "must be an object"),
        ("choose", {"options": 5}, "array"),
        ("choose", {"options": [None, "", "  "]}, "no usable options"),
        ("show_table", {"rows": ["a", 1]}, "no object rows"),
        ("show_chart", {"values": ["x", None]}, "no numeric values"),
        ("launch_run", {"kind": "deploy", "config": {"a": 1}}, "kind"),
        ("launch_run", {"kind": "eval", "config": {"a": None}}, "no usable config"),
        ("launch_run", {"kind": "eval", "config": "x"}, "must be an object"),
        ("show_patch", {"patch": "   "}, "empty"),
        ("show_patch", {}, "required"),
    ],
)
def test_rejections(name, args, reason):
    with pytest.raises(WidgetValidationError, match=reason):
        normalize_widget_call(name, args)
    # and the renderer turns the same payload into an error panel, not a crash
    text = _render_text(render_widget(name, args))
    assert "widget error" in text


def test_malformed_battery_never_crashes_render():
    """Adversarial battery: every payload must produce SOME panel."""
    battery = [
        ("choose", {"options": [{"nested": "obj"}] * 3}),
        ("choose", {"options": ["ok"], "title": ["list", "title"]}),
        ("show_table", {"rows": [{"k" * 500: "v" * 500}]}),
        ("show_table", {"rows": [{1: 2, None: 3}]}),
        ("show_chart", {"values": [1e308, -1e308, 0]}),
        ("show_chart", {"values": [True, False]}),
        ("launch_run", {"kind": "eval", "config": {"limit": math.inf}}),
        ("show_patch", {"patch": 12345}),
        ("launch_run", {"kind": None, "config": None}),
        ("show_chart", {"values": {}}),
    ]
    for name, args in battery:
        text = _render_text(render_widget(name, args))
        assert text.strip(), (name, args)


# -- state round-trip ----------------------------------------------------------


def test_interaction_stamps_survive_renormalization():
    """The chat screen stamps selected/saved_card into rendered args; a
    re-render of the transcript re-normalizes — stamps must survive."""
    args = {"options": ["a", None, "b"], "selected": "b"}
    first = normalize_widget_call("choose", args)
    assert first.args["selected"] == "b"
    second = normalize_widget_call("choose", first.args)
    assert second.args["selected"] == "b"
    assert second.args["options"] == ["a", "b"]
    text = _render_text(render_widget("choose", second.args))
    assert "✓" in text  # selection marker rendered

    launch = {"kind": "eval", "config": {"limit": "4"}, "saved_card": "card-1.toml"}
    normalized = normalize_widget_call("launch_run", launch)
    assert normalized.args["saved_card"] == "card-1.toml"
    text = _render_text(render_widget("launch_run", normalized.args))
    assert "card written" in text


def test_normalization_is_idempotent():
    """Repair then re-normalize: second pass makes no further repairs."""
    cases = [
        ("choose", {"options": ["a", 3, None]}),
        ("show_chart", {"values": ["1", 2, "junk"]}),
        ("launch_run", {"kind": "training", "config": {"limit": "8", "x": None}}),
        ("show_table", {"rows": [{"a": 1}, "junk"]}),
    ]
    for name, args in cases:
        first = normalize_widget_call(name, args)
        second = normalize_widget_call(name, first.args)
        assert second.repairs == (), (name, second.repairs)
        assert second.args == first.args


# -- card lifecycle ------------------------------------------------------------


def test_launch_card_payload_maps_kind_and_types():
    normalized = normalize_widget_call(
        "launch_run",
        {"kind": "training", "config": {"model": "m", "limit": "16", "learning_rate": "3e-4"}},
    )
    kind, payload = launch_card_payload(normalized)
    assert kind == "train"
    assert payload == {"model": "m", "limit": 16, "learning_rate": 3e-4}
    with pytest.raises(WidgetValidationError, match="not a launch proposal"):
        launch_card_payload(NormalizedWidget(name="choose", args={}))


def test_chat_proposal_writes_typed_card(tmp_path):
    """End-to-end card lifecycle: agent proposal -> typed card on disk ->
    scan_cards sees it -> TOML round-trips with real types."""
    import tomllib

    from prime_tpu.lab.tui.chat import AgentChatScreen
    from prime_tpu.lab.tui.launch import scan_cards

    screen = AgentChatScreen("tester", lambda: None, workspace=str(tmp_path))
    screen.pending = {
        "name": "launch_run",
        "args": {
            "kind": "training",
            "config": {"model": "llama3-8b", "limit": "32", "temperature": "0.5", "bad": None},
        },
    }
    message = screen._act_on_pending()
    assert "launch card written" in message
    cards = scan_cards(tmp_path)
    assert len(cards) == 1
    card = cards[0]
    assert card.kind == "train"
    parsed = tomllib.loads(card.path.read_text())
    payload = parsed["train"]  # card TOML: [launch] header + [<kind>] payload
    assert payload["limit"] == 32 and isinstance(payload["limit"], int)
    assert payload["temperature"] == 0.5 and isinstance(payload["temperature"], float)


def test_chat_unusable_proposal_writes_nothing(tmp_path):
    from prime_tpu.lab.tui.chat import AgentChatScreen
    from prime_tpu.lab.tui.launch import scan_cards

    screen = AgentChatScreen("tester", lambda: None, workspace=str(tmp_path))
    screen.pending = {
        "name": "launch_run",
        "args": {"kind": "eval", "config": {"everything": None}},
    }
    message = screen._act_on_pending()
    assert "unusable proposal" in message
    assert scan_cards(tmp_path) == []


# -- configure_run form model (VERDICT r4 #3: reference field-spec layer) -----


def _form(args, workspace=None):
    from prime_tpu.lab.widget_model import build_form_model, normalize_widget_call

    return build_form_model(normalize_widget_call("configure_run", args), workspace)


def test_form_normalization_maps_kind_and_coerces_config():
    from prime_tpu.lab.widget_model import normalize_widget_call

    normalized = normalize_widget_call(
        "configure_run",
        {"kind": "training", "env": 7, "config": {"limit": "20", "junk": None}},
    )
    assert normalized.args["kind"] == "rl"
    assert normalized.args["env"] == "7"
    assert normalized.args["config"]["limit"] == 20
    assert "junk" not in normalized.args["config"]
    assert any("mapped" in r for r in normalized.repairs)
    with pytest.raises(WidgetValidationError, match="kind"):
        normalize_widget_call("configure_run", {"kind": "pods"})


def test_form_defaults_and_layering():
    form = _form({"kind": "eval", "env": "gsm8k"})
    by_name = {f.name: f for f in form.fields}
    assert by_name["limit"].value == "50"            # seeded default
    assert by_name["rollouts_per_example"].value == "3"
    assert by_name["max_concurrent"].value == "auto"
    assert by_name["env"].value == "gsm8k"
    assert form.title == "Evaluate gsm8k"
    assert [a.name for a in form.actions] == ["launch", "stop"]

    # agent config beats defaults; user edits beat agent config
    form = _form(
        {"kind": "eval", "config": {"limit": 10}, "form_values": {"limit": "99"}}
    )
    assert {f.name: f for f in form.fields}["limit"].value == "99"


def test_form_rl_schedule_and_disabled_field():
    form = _form({"kind": "rl", "env": "arith-rl"})
    names = [f.name for f in form.fields]
    assert "max_steps" in names and "batch_size" in names
    assert "seq_len" not in names  # disabled + no value -> omitted
    form = _form({"kind": "rl", "config": {"seq_len": 2048}})
    seq = {f.name: f for f in form.fields}["seq_len"]
    assert seq.disabled and seq.value == "2048"
    assert form.title.startswith("Train")


def test_form_model_select_options(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "endpoints.toml").write_text(
        '[fast]\nmodel = "llama3.2-1b"\nbase_url = "https://x/v1"\n'
    )
    form = _form({"kind": "eval"}, workspace=tmp_path)
    model = {f.name: f for f in form.fields}["model"]
    assert model.widget == "select"
    values = [v for _, v in model.options]
    assert "llama3.2-1b" in values          # preset registry
    assert "fast" in values                 # endpoint alias
    assert model.value == values[0]         # seeded with the first option

    # rl forms restrict to trainable presets (no serving aliases)
    rl_model = {f.name: f for f in _form({"kind": "rl"}, workspace=tmp_path).fields}["model"]
    assert "fast" not in [v for _, v in rl_model.options]

    # an unknown agent-proposed model is kept, prepended to the options
    form = _form({"kind": "eval", "config": {"model": "my-finetune"}}, workspace=tmp_path)
    model = {f.name: f for f in form.fields}["model"]
    assert model.value == "my-finetune" and model.options[0] == ("my-finetune", "my-finetune")


def test_form_environment_select_from_workspace(tmp_path):
    from prime_tpu.envhub.packaging import write_env_template

    write_env_template(tmp_path / "environments" / "wordle", "wordle")
    write_env_template(tmp_path / "environments" / "maze", "maze")
    form = _form({"kind": "eval"}, workspace=tmp_path)
    env = {f.name: f for f in form.fields}["env"]
    assert env.widget == "select"
    assert [v for _, v in env.options] == ["maze", "wordle"]
    assert env.value == "maze"


def test_form_parse_and_launch_payload():
    from prime_tpu.lab.widget_model import form_launch_payload, parse_form_values

    form = _form({"kind": "eval", "env": "gsm8k", "form_values": {"limit": "abc"}})
    _config, errors = parse_form_values(form)
    assert errors and "Examples" in errors[0]
    with pytest.raises(WidgetValidationError, match="Examples"):
        form_launch_payload(form)

    form = _form({"kind": "rl", "env": "arith-rl", "config": {"model": "tiny-test"}})
    kind, payload = form_launch_payload(form)
    assert kind == "train"                       # rl maps onto the card taxonomy
    assert payload["max_steps"] == 100 and isinstance(payload["max_steps"], int)
    assert payload["env"] == "arith-rl"

    with pytest.raises(WidgetValidationError, match="Environment"):
        form_launch_payload(_form({"kind": "eval"}))
    with pytest.raises(WidgetValidationError, match="command line"):
        form_launch_payload(_form({"kind": "gepa", "env": "wordle"}))


def test_form_command_text():
    from prime_tpu.lab.widget_model import form_command_text

    assert (
        form_command_text(_form({"kind": "eval", "env": "gsm8k", "config": {"model": "m1"}}))
        == "prime eval run gsm8k -m m1 -n 50 --max-new-tokens 1024"
    )
    assert form_command_text(
        _form({"kind": "gepa", "env": "wordle", "config": {"model": "m1"}})
    ) == "prime gepa run wordle -m m1"
    assert "train request" in form_command_text(_form({"kind": "rl", "env": "e"}))


def test_form_state_round_trips():
    from prime_tpu.lab.widget_model import normalize_widget_call

    args = {
        "kind": "eval",
        "env": "gsm8k",
        "form_values": {"limit": "7"},
        "form_errors": ["Examples: 'x' is not an integer"],
        "saved_card": "chat-form.toml",
    }
    normalized = normalize_widget_call("configure_run", args)
    assert normalized.args["form_values"] == {"limit": "7"}
    assert normalized.args["form_errors"] == ["Examples: 'x' is not an integer"]
    assert normalized.args["saved_card"] == "chat-form.toml"
    # idempotent: re-normalizing the normalized args changes nothing
    again = normalize_widget_call("configure_run", normalized.args)
    assert again.args == normalized.args


def test_form_renders_headlessly(tmp_path):
    import io

    from rich.console import Console

    from prime_tpu.lab.widgets import render_widget

    console = Console(width=90, file=io.StringIO(), force_terminal=False)
    console.print(
        render_widget(
            "configure_run",
            {"kind": "eval", "env": "gsm8k", "form_errors": ["Examples: bad"]},
            workspace=tmp_path,
        )
    )
    out = console.file.getvalue()
    assert "Evaluate gsm8k" in out
    assert "Examples" in out and "50" in out
    assert "bad" in out
    # error payload renders as an explicit error panel, never a crash
    console = Console(width=90, file=io.StringIO(), force_terminal=False)
    console.print(render_widget("configure_run", {"kind": "nope"}))
    assert "widget error" in console.file.getvalue()


def test_form_extras_visible_and_carried_to_card():
    """Agent config outside the schedule (temperature, seed) must ride onto
    the launched card — a proposal can't behave differently between
    launch_run and configure_run — and render in the form."""
    import io

    from rich.console import Console

    from prime_tpu.lab.widget_model import form_launch_payload
    from prime_tpu.lab.widgets import render_widget

    args = {"kind": "eval", "env": "gsm8k", "config": {"temperature": 0.0, "seed": 42}}
    form = _form(args)
    assert dict(form.extras) == {"temperature": 0.0, "seed": 42}
    _kind, payload = form_launch_payload(form)
    assert payload["temperature"] == 0.0 and payload["seed"] == 42
    console = Console(width=90, file=io.StringIO(), force_terminal=False)
    console.print(render_widget("configure_run", args))
    out = console.file.getvalue()
    assert "temperature" in out and "seed" in out


def test_gepa_form_stamps_command_not_card(tmp_path):
    import io

    from rich.console import Console

    from prime_tpu.lab.tui.chat import AgentChatScreen
    from prime_tpu.lab.tui.launch import scan_cards
    from prime_tpu.lab.widgets import render_widget

    screen = AgentChatScreen("tester", lambda: None, workspace=str(tmp_path))
    entry = {"role": "widget", "name": "configure_run",
             "args": {"kind": "gepa", "env": "wordle", "config": {"model": "m1"}}}
    screen.transcript.append(entry)
    screen.pending = entry
    status = screen.on_key("enter")
    assert status == "prime gepa run wordle -m m1"
    assert "saved_card" not in entry["args"]
    assert entry["args"]["command"] == status
    assert scan_cards(tmp_path) == []  # truly no card on disk
    console = Console(width=100, file=io.StringIO(), force_terminal=False)
    console.print(render_widget("configure_run", entry["args"]))
    out = console.file.getvalue()
    assert "command sent" in out and "card written" not in out
