"""Agent runtime (subprocess stdio chat) + MCP server protocol tests."""

import io
import json
import subprocess
import sys
import textwrap

import pytest

from prime_tpu.lab.agents import AgentError, AgentRuntime
from prime_tpu.lab.mcp import build_tools, handle_request


# -- scripted fake agents ------------------------------------------------------

SIMPLE_AGENT = textwrap.dedent(
    """
    import json, sys
    for line in sys.stdin:
        msg = json.loads(line)
        if msg.get("type") == "prompt":
            for word in msg["text"].split():
                print(json.dumps({"type": "chunk", "text": word.upper() + " "}), flush=True)
            print(json.dumps({"type": "done", "id": msg["id"]}), flush=True)
    """
)

ACP_AGENT = textwrap.dedent(
    """
    import json, sys
    def send(obj):
        print(json.dumps(obj), flush=True)
    for line in sys.stdin:
        msg = json.loads(line)
        method = msg.get("method")
        if method == "initialize":
            send({"jsonrpc": "2.0", "id": msg["id"], "result": {"protocolVersion": 1}})
        elif method == "session/new":
            send({"jsonrpc": "2.0", "id": msg["id"], "result": {"sessionId": "sess-1"}})
        elif method == "session/prompt":
            text = msg["params"]["prompt"][0]["text"]
            assert msg["params"]["sessionId"] == "sess-1"
            for chunk in (text[:3], text[3:]):
                send({"jsonrpc": "2.0", "method": "session/update",
                      "params": {"update": {"sessionUpdate": "agent_message_chunk",
                                             "content": {"type": "text", "text": chunk}}}})
            send({"jsonrpc": "2.0", "id": msg["id"], "result": {"stopReason": "end_turn"}})
    """
)

CRASHING_AGENT = "import sys; sys.exit(3)"


def _agent(script: str, dialect: str) -> AgentRuntime:
    return AgentRuntime([sys.executable, "-u", "-c", script], dialect=dialect)


def test_simple_dialect_chat():
    with _agent(SIMPLE_AGENT, "simple") as agent:
        assert agent.chat("hello tpu world", timeout_s=20) == "HELLO TPU WORLD "
        # second turn on the same process
        assert agent.chat("again", timeout_s=20) == "AGAIN "


def test_acp_dialect_handshake_and_chat():
    with _agent(ACP_AGENT, "acp") as agent:
        assert agent.dialect.session_id == "sess-1"
        assert agent.chat("ping-pong", timeout_s=20) == "ping-pong"


def test_agent_crash_is_detected():
    agent = _agent(CRASHING_AGENT, "simple")
    agent.start()
    with pytest.raises(AgentError, match="exited|closed"):
        agent.chat("anything", timeout_s=10)
    agent.close()


def test_unknown_dialect_rejected():
    with pytest.raises(AgentError, match="unknown dialect"):
        AgentRuntime(["true"], dialect="letta-v9")


def test_agent_turn_timeout():
    hang = "import sys\nfor line in sys.stdin: pass"
    agent = AgentRuntime([sys.executable, "-u", "-c", hang], dialect="simple")
    agent.start()
    with pytest.raises(AgentError, match="timed out"):
        agent.chat("no reply", timeout_s=1.0)
    agent.close()


# -- MCP server ---------------------------------------------------------------


def _rpc(method, params=None, request_id=1):
    msg = {"jsonrpc": "2.0", "id": request_id, "method": method}
    if params is not None:
        msg["params"] = params
    return msg


def test_mcp_initialize_and_tools_list(tmp_path):
    tools = build_tools(str(tmp_path))
    response = handle_request(_rpc("initialize"), tools)
    assert response["result"]["serverInfo"]["name"] == "prime-lab"
    listing = handle_request(_rpc("tools/list"), tools)
    names = {t["name"] for t in listing["result"]["tools"]}
    assert {"lab_snapshot", "lab_eval_runs", "lab_launch_cards", "lab_hygiene"} <= names


def test_mcp_tool_call_eval_runs(tmp_path):
    run_dir = tmp_path / "outputs" / "evals" / "arith--m" / "r1"
    run_dir.mkdir(parents=True)
    (run_dir / "metadata.json").write_text(json.dumps({"metrics": {"accuracy": 1.0}}))
    tools = build_tools(str(tmp_path))
    response = handle_request(
        _rpc("tools/call", {"name": "lab_eval_runs", "arguments": {}}), tools
    )
    rows = json.loads(response["result"]["content"][0]["text"])
    assert rows[0]["env"] == "arith" and rows[0]["accuracy"] == 1.0


def test_mcp_unknown_tool_and_method(tmp_path):
    tools = build_tools(str(tmp_path))
    bad_tool = handle_request(_rpc("tools/call", {"name": "nope"}), tools)
    assert bad_tool["error"]["code"] == -32602
    bad_method = handle_request(_rpc("frobnicate"), tools)
    assert bad_method["error"]["code"] == -32601
    assert handle_request({"jsonrpc": "2.0", "method": "notifications/initialized"}, tools) is None


def test_mcp_tool_error_is_in_band(tmp_path, monkeypatch):
    tools = build_tools(str(tmp_path / "missing-dir"))
    response = handle_request(
        _rpc("tools/call", {"name": "lab_hygiene", "arguments": {}}), tools
    )
    payload = response["result"]
    assert payload.get("isError") is True
    assert "error" in payload["content"][0]["text"]


def test_mcp_stdio_end_to_end(tmp_path):
    """Spawn the real `prime lab mcp` process and speak the protocol."""
    messages = "\n".join(
        json.dumps(m)
        for m in [
            _rpc("initialize", request_id=1),
            {"jsonrpc": "2.0", "method": "notifications/initialized"},
            _rpc("tools/list", request_id=2),
            _rpc("tools/call", {"name": "lab_launch_cards", "arguments": {}}, request_id=3),
        ]
    )
    proc = subprocess.run(
        [sys.executable, "-m", "prime_tpu.commands.main", "lab", "mcp", "--dir", str(tmp_path)],
        input=messages + "\n",
        capture_output=True,
        text=True,
        timeout=60,
        cwd="/root/repo",
    )
    responses = [json.loads(line) for line in proc.stdout.splitlines() if line.strip()]
    assert len(responses) == 3  # notification produced no response
    assert responses[0]["result"]["protocolVersion"]
    assert json.loads(responses[2]["result"]["content"][0]["text"]) == []


# -- CLI agent turn -----------------------------------------------------------


def test_lab_agent_cli_one_turn(tmp_path):
    from click.testing import CliRunner

    from prime_tpu.commands.main import cli

    script = tmp_path / "agent.py"
    script.write_text(SIMPLE_AGENT)
    result = CliRunner().invoke(
        cli,
        ["lab", "agent", "hello world", "--dialect", "simple",
         "--command", f"{sys.executable} -u {script}"],
    )
    assert result.exit_code == 0, result.output
    assert "HELLO WORLD" in result.output


def test_agent_nonobject_json_does_not_kill_reader():
    weird = textwrap.dedent(
        """
        import json, sys
        print("null", flush=True)
        print("[1,2,3]", flush=True)
        for line in sys.stdin:
            msg = json.loads(line)
            if msg.get("type") == "prompt":
                print(json.dumps({"type": "chunk", "text": "ok"}), flush=True)
                print(json.dumps({"type": "done"}), flush=True)
        """
    )
    with AgentRuntime([sys.executable, "-u", "-c", weird], dialect="simple") as agent:
        assert agent.chat("x", timeout_s=20) == "ok"


def test_stale_turn_events_are_drained():
    slow = textwrap.dedent(
        """
        import json, sys, time
        for line in sys.stdin:
            msg = json.loads(line)
            if msg.get("type") != "prompt":
                continue
            text = msg["text"]
            if text == "warmup":
                print(json.dumps({"type": "chunk", "text": "ok"}), flush=True)
            elif text == "turn1":
                time.sleep(2)  # answer turn 1 late
                print(json.dumps({"type": "chunk", "text": "STALE"}), flush=True)
            else:
                print(json.dumps({"type": "chunk", "text": "fresh"}), flush=True)
            print(json.dumps({"type": "done"}), flush=True)
        """
    )
    agent = AgentRuntime([sys.executable, "-u", "-c", slow], dialect="simple")
    agent.start()
    assert agent.chat("warmup", timeout_s=30) == "ok"  # agent fully up
    with pytest.raises(AgentError, match="timed out"):
        agent.chat("turn1", timeout_s=0.5)
    import time as _time

    _time.sleep(2.5)  # let the stale answer land in the queue
    assert agent.chat("turn2", timeout_s=20) == "fresh"
    agent.close()


def test_mcp_rejects_nonobject_requests(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "prime_tpu.commands.main", "lab", "mcp", "--dir", str(tmp_path)],
        input='[1,2]\n"str"\n' + json.dumps(_rpc("tools/list", request_id=9)) + "\n",
        capture_output=True,
        text=True,
        timeout=60,
        cwd="/root/repo",
    )
    responses = [json.loads(line) for line in proc.stdout.splitlines() if line.strip()]
    assert responses[0]["error"]["code"] == -32600
    assert responses[1]["error"]["code"] == -32600
    assert "tools" in responses[2]["result"]  # server survived bad input


# -- codex app-server dialect (VERDICT r2 #4) ---------------------------------

CODEX_AGENT = textwrap.dedent(
    """
    import json, sys
    def send(obj):
        print(json.dumps(obj), flush=True)
    for line in sys.stdin:
        msg = json.loads(line)
        method = msg.get("method")
        if method == "initialize":
            send({"jsonrpc": "2.0", "id": msg["id"], "result": {}})
        elif method == "thread/start":
            assert isinstance(msg["params"].get("dynamicTools"), list)
            assert any(t["name"] == "choose" for t in msg["params"]["dynamicTools"])
            send({"jsonrpc": "2.0", "id": msg["id"], "result": {"thread": {"id": "th-1"}}})
        elif method == "turn/start":
            assert msg["params"]["threadId"] == "th-1"
            text = msg["params"]["input"][0]["text"]
            for piece in (text[:2], text[2:]):
                send({"jsonrpc": "2.0", "method": "item/agentMessage/delta",
                      "params": {"delta": piece, "turnId": "t1"}})
            send({"jsonrpc": "2.0", "method": "item/tool/call",
                  "params": {"name": "show_chart", "arguments": {"values": [1, 2, 3]}}})
            send({"jsonrpc": "2.0", "method": "turn/completed", "params": {"turn": {}}})
    """
)

LETTA_AGENT = textwrap.dedent(
    """
    import json, sys
    def send(obj):
        print(json.dumps(obj), flush=True)
    send({"type": "system", "session_id": "lt-1"})
    for line in sys.stdin:
        msg = json.loads(line)
        if msg.get("type") == "control_request":
            continue  # client-initiated init/register: no reply needed
        if msg.get("type") == "control_response":
            continue
        if msg.get("type") == "user":
            text = msg["message"]["content"]
            # ask permission first; the client must auto-allow
            send({"type": "control_request", "request_id": "r1",
                  "request": {"subtype": "can_use_tool", "tool_name": "choose"}})
            granted = json.loads(input())
            assert granted["response"]["response"]["behavior"] == "allow"
            send({"type": "control_request", "request_id": "r2",
                  "request": {"subtype": "execute_external_tool",
                               "tool_name": "choose",
                               "arguments": {"options": ["a", "b"]}}})
            ack = json.loads(input())
            assert ack["response"]["response"]["status"] == "rendered"
            send({"type": "assistant", "message": {"role": "assistant",
                  "content": [{"type": "text", "text": text[::-1]}]}})
            send({"type": "result"})
    """
)


def test_codex_dialect_chat_and_widget():
    with _agent(CODEX_AGENT, "codex") as agent:
        events = list(agent.prompt("hello", timeout_s=20))
    text = "".join(e.text for e in events if e.kind == "chunk")
    widgets = [e.widget for e in events if e.kind == "widget"]
    assert text == "hello"
    assert widgets == [{"name": "show_chart", "args": {"values": [1, 2, 3]}}]
    # handshake captured the thread id
    assert agent.dialect.session_id == "th-1"


def test_codex_turn_error_raises():
    script = textwrap.dedent(
        """
        import json, sys
        def send(obj):
            print(json.dumps(obj), flush=True)
        for line in sys.stdin:
            msg = json.loads(line)
            if msg.get("method") == "initialize":
                send({"jsonrpc": "2.0", "id": msg["id"], "result": {}})
            elif msg.get("method") == "thread/start":
                send({"jsonrpc": "2.0", "id": msg["id"], "result": {"thread": {"id": "t"}}})
            elif msg.get("method") == "turn/start":
                send({"jsonrpc": "2.0", "method": "turn/completed",
                      "params": {"turn": {"error": {"message": "model overloaded"}}}})
        """
    )
    with _agent(script, "codex") as agent:
        with pytest.raises(AgentError, match="model overloaded"):
            list(agent.prompt("hi", timeout_s=20))


def test_letta_dialect_auto_allows_tools_and_streams():
    with _agent(LETTA_AGENT, "letta") as agent:
        events = list(agent.prompt("abc", timeout_s=20))
    text = "".join(e.text for e in events if e.kind == "chunk")
    widgets = [e.widget for e in events if e.kind == "widget"]
    assert text == "cba"
    assert widgets == [{"name": "choose", "args": {"options": ["a", "b"]}}]
    assert agent.dialect.session_id == "lt-1"


# -- widget contract -----------------------------------------------------------


def test_widget_specs_cover_both_wire_shapes():
    from prime_tpu.lab.widgets import WIDGET_TOOLS, letta_external_tools, widget_tool_specs

    names = {t.name for t in WIDGET_TOOLS}
    assert {"choose", "show_table", "show_chart", "launch_run", "show_patch"} <= names
    codex = widget_tool_specs()
    letta = letta_external_tools()
    assert {t["name"] for t in codex} == names == {t["name"] for t in letta}
    assert all("parameters" in t for t in codex)
    assert all(t["label"].startswith("Lab ") for t in letta)


def test_widget_render_and_validation():
    from rich.console import Console

    from prime_tpu.lab.widgets import render_widget, validate_widget_call

    assert validate_widget_call("choose", {}) is not None          # missing options
    assert validate_widget_call("choose", {"options": ["x"]}) is None
    assert validate_widget_call("nope", {}) is not None
    console = Console(width=80, file=io.StringIO(), force_terminal=False)
    console.print(render_widget("show_table", {"rows": [{"a": 1, "b": 2}]}))
    console.print(render_widget("show_chart", {"values": [1.0, 5.0, 2.0]}))
    console.print(render_widget("choose", {"options": ["first", "second"]}))
    console.print(render_widget("bad_tool", {}))
    out = console.file.getvalue()
    assert "first" in out and "widget error" in out


# -- in-shell chat screen ------------------------------------------------------


class _ScriptedRuntime:
    """Deterministic in-process stand-in for AgentRuntime."""

    def __init__(self):
        self.started = False
        self.closed = False

    def start(self):
        self.started = True

    def close(self):
        self.closed = True

    def prompt(self, text, timeout_s=120.0):
        from prime_tpu.lab.agents import AgentEvent

        yield AgentEvent("chunk", text=f"echo:{text}")
        yield AgentEvent("widget", widget={"name": "choose", "args": {"options": ["x", "y"]}})


def test_chat_screen_turn_and_widget_render():
    from rich.console import Console

    from prime_tpu.lab.tui.chat import AgentChatScreen

    screen = AgentChatScreen("tester", _ScriptedRuntime)
    for ch in "hi!":
        screen.on_key(ch)
    assert screen.input_buffer == "hi!"
    screen.on_key("enter")
    assert screen.wait_idle(5)
    roles = [e["role"] for e in screen.transcript]
    assert roles == ["user", "assistant", "widget"]
    assert screen.transcript[1]["text"] == "echo:hi!"
    console = Console(width=90, file=io.StringIO(), force_terminal=False)
    console.print(screen.render())
    out = console.file.getvalue()
    assert "echo:hi!" in out and "choose" in out


def test_chat_screen_esc_clears_then_closes():
    from prime_tpu.lab.tui.chat import AgentChatScreen
    from prime_tpu.lab.tui.detail import CLOSE

    runtime = _ScriptedRuntime()
    screen = AgentChatScreen("tester", lambda: runtime)
    screen.on_key("x")
    assert screen.on_key("escape") is None and screen.input_buffer == ""
    screen.on_key("h")
    screen.on_key("enter")
    assert screen.wait_idle(5)
    assert screen.on_key("escape") == CLOSE
    assert runtime.closed


class _WidgetScriptRuntime:
    """Emits a launch proposal for 'launch', otherwise echo + a choose."""

    def start(self):
        pass

    def close(self):
        pass

    def prompt(self, text, timeout_s=120.0):
        from prime_tpu.lab.agents import AgentEvent

        if text == "launch":
            yield AgentEvent(
                "widget",
                widget={
                    "name": "launch_run",
                    "args": {
                        "kind": "eval",
                        "config": {"env": "gsm8k", "model": "m1", "nested": {"x": 1}},
                    },
                },
            )
        else:
            yield AgentEvent("chunk", text=f"echo:{text}")
            yield AgentEvent("widget", widget={"name": "choose", "args": {"options": ["x", "y"]}})


def test_chat_choice_selection_roundtrip():
    from rich.console import Console

    from prime_tpu.lab.tui.chat import AgentChatScreen

    screen = AgentChatScreen("tester", _WidgetScriptRuntime)
    screen.on_key("h")
    screen.on_key("enter")
    assert screen.wait_idle(5)
    assert screen.pending is not None and screen.pending["name"] == "choose"
    # pending cursor renders as a marker
    console = Console(width=90, file=io.StringIO(), force_terminal=False)
    console.print(screen.render())
    assert "▸" in console.file.getvalue()
    screen.on_key("down")          # cursor -> y
    screen.on_key("enter")         # select: answer goes back as a user turn
    assert screen.wait_idle(5)
    widget = next(e for e in screen.transcript if e["role"] == "widget")
    assert widget["args"]["selected"] == "y"
    texts = [e.get("text") for e in screen.transcript if e.get("role") == "user"]
    assert "y" in texts
    assert any(e.get("text") == "echo:y" for e in screen.transcript)


def test_chat_launch_proposal_writes_card(tmp_path):
    import tomllib

    from prime_tpu.lab.tui.chat import AgentChatScreen

    screen = AgentChatScreen("tester", _WidgetScriptRuntime, workspace=str(tmp_path))
    for ch in "launch":
        screen.on_key(ch)
    screen.on_key("enter")
    assert screen.wait_idle(5)
    assert screen.pending is not None and screen.pending["name"] == "launch_run"
    status = screen.on_key("enter")    # act on the proposal
    assert "launch card written" in status
    assert screen.pending is None
    card_path = tmp_path / ".prime-lab" / "launch" / "tester-proposal.toml"
    data = tomllib.loads(card_path.read_text())
    assert data["launch"]["kind"] == "eval"
    assert data["eval"] == {"env": "gsm8k", "model": "m1"}   # nested value filtered
    widget = next(e for e in screen.transcript if e["role"] == "widget")
    assert widget["args"]["saved_card"] == "tester-proposal.toml"


def test_chat_launch_kind_normalized_and_bad_kind_rejected(tmp_path):
    import tomllib

    from prime_tpu.lab.tui.chat import AgentChatScreen

    screen = AgentChatScreen("tester", _WidgetScriptRuntime, workspace=str(tmp_path))
    # kind='training' (widget enum) must become a 'train' card scan_cards accepts
    screen.transcript.append(
        {"role": "widget", "name": "launch_run",
         "args": {"kind": "training", "config": {"model": "m1", "steps": 5}}}
    )
    screen.pending = screen.transcript[-1]
    status = screen.on_key("enter")
    assert "launch card written" in status
    card = tmp_path / ".prime-lab" / "launch" / "tester-proposal.toml"
    data = tomllib.loads(card.read_text())
    assert data["launch"]["kind"] == "train" and data["train"]["steps"] == 5
    from prime_tpu.lab.tui.launch import scan_cards

    assert any(c.kind == "train" for c in scan_cards(tmp_path))
    # unsupported kind is refused, not silently lost
    screen.transcript.append(
        {"role": "widget", "name": "launch_run", "args": {"kind": "pod", "config": {"x": 1}}}
    )
    screen.pending = screen.transcript[-1]
    assert "eval' or 'training" in screen.on_key("enter")


def test_chat_launch_without_config_refused(tmp_path):
    from prime_tpu.lab.tui.chat import AgentChatScreen

    screen = AgentChatScreen("tester", _WidgetScriptRuntime, workspace=str(tmp_path))
    screen.transcript.append(
        {"role": "widget", "name": "launch_run", "args": {"kind": "eval"}}
    )
    screen.pending = screen.transcript[-1]
    status = screen.on_key("enter")
    assert "unusable proposal" in status
    # no template-default card was fabricated
    assert not (tmp_path / ".prime-lab" / "launch").exists()


def test_chat_whitespace_enter_acts_and_selection_matches_render():
    """Selection acts on the NORMALIZED options — the exact list the panel
    renders (a blank option is dropped by the widget model, so the cursor
    lands on the only real option and the agent receives its label, not a
    positional answer for an entry the UI never showed)."""
    from prime_tpu.lab.tui.chat import AgentChatScreen

    screen = AgentChatScreen("tester", _WidgetScriptRuntime)
    screen.transcript.append(
        {"role": "widget", "name": "choose", "args": {"options": ["", "retry"]}}
    )
    screen.pending = screen.transcript[-1]
    screen.on_key(" ")             # stray whitespace then enter still selects
    status = screen.on_key("enter")
    assert "selected" in status
    assert screen.wait_idle(5)
    user_turns = [e["text"] for e in screen.transcript if e.get("role") == "user"]
    assert user_turns == ["retry"]
    # all options unusable -> the widget refuses rather than misrendering
    screen.transcript.append(
        {"role": "widget", "name": "choose", "args": {"options": ["", "  "]}}
    )
    screen.pending = screen.transcript[-1]
    assert "no options" in screen.on_key("enter")


def test_chat_free_text_overrides_pending_choice():
    from prime_tpu.lab.tui.chat import AgentChatScreen

    screen = AgentChatScreen("tester", _WidgetScriptRuntime)
    screen.on_key("h")
    screen.on_key("enter")
    assert screen.wait_idle(5)
    first_widget = screen.pending
    for ch in "neither":
        screen.on_key(ch)
    screen.on_key("enter")         # typed reply, not a selection
    assert screen.wait_idle(5)
    assert "selected" not in first_widget["args"]
    assert any(e.get("text") == "neither" for e in screen.transcript)


def test_chat_section_lists_configured_agents(tmp_path):
    from prime_tpu.lab.tui.chat import load_agents_config

    cfg_dir = tmp_path / ".prime-lab"
    cfg_dir.mkdir()
    (cfg_dir / "agents.json").write_text(
        json.dumps({"agents": [
            {"name": "codex", "command": "codex app-server", "dialect": "codex"},
            {"name": "broken"},  # no command: skipped
        ]})
    )
    rows = load_agents_config(tmp_path)
    assert rows == [{"name": "codex", "dialect": "codex", "command": "codex app-server"}]
    assert load_agents_config(tmp_path / "nope") == []


# -- MCP widget + detail tools -------------------------------------------------


def test_mcp_widget_tools_journal(tmp_path):
    tools = build_tools(str(tmp_path))
    listed = handle_request({"jsonrpc": "2.0", "id": 1, "method": "tools/list"}, tools)
    names = {t["name"] for t in listed["result"]["tools"]}
    assert {"lab_widget_choose", "lab_widget_show_chart", "lab_training_runs",
            "lab_eval_samples"} <= names
    good = handle_request(
        {"jsonrpc": "2.0", "id": 2, "method": "tools/call",
         "params": {"name": "lab_widget_choose", "arguments": {"options": ["a"]}}},
        tools,
    )
    payload = json.loads(good["result"]["content"][0]["text"])
    assert payload["status"] == "rendered"
    journal = (tmp_path / ".prime-lab" / "widgets.jsonl").read_text().strip()
    assert json.loads(journal) == {"name": "choose", "args": {"options": ["a"]}}
    bad = handle_request(
        {"jsonrpc": "2.0", "id": 3, "method": "tools/call",
         "params": {"name": "lab_widget_choose", "arguments": {}}},
        tools,
    )
    assert json.loads(bad["result"]["content"][0]["text"])["status"] == "invalid"


def test_mcp_eval_samples_tool(tmp_path):
    run_dir = tmp_path / "outputs" / "evals" / "gsm8k--m1" / "r7"
    run_dir.mkdir(parents=True)
    (run_dir / "metadata.json").write_text(json.dumps({"metrics": {"accuracy": 1.0}}))
    (run_dir / "results.jsonl").write_text(
        json.dumps({"prompt": "p", "completion": "c", "reward": 1.0}) + "\n"
    )
    tools = build_tools(str(tmp_path))
    response = handle_request(
        {"jsonrpc": "2.0", "id": 4, "method": "tools/call",
         "params": {"name": "lab_eval_samples", "arguments": {"runId": "r7"}}},
        tools,
    )
    samples = json.loads(response["result"]["content"][0]["text"])
    assert samples[0]["prompt"] == "p"


def test_chat_form_edit_launch_roundtrip(tmp_path):
    """configure_run form: field edits stamp form_values, typed errors stay
    on the form, a valid enter writes the launch card (VERDICT r4 #3)."""
    import tomllib

    from prime_tpu.lab.tui.chat import AgentChatScreen

    screen = AgentChatScreen("tester", lambda: None, workspace=str(tmp_path))
    screen.transcript.append(
        {"role": "widget", "name": "configure_run",
         "args": {"kind": "eval", "env": "gsm8k", "config": {"model": "tiny-test"}}}
    )
    screen.pending = screen.transcript[-1]

    # a field edit is intercepted (not sent to the agent) and stamped
    for ch in "limit=abc":
        screen.on_key(ch)
    status = screen.on_key("enter")
    assert status == "limit = abc"
    assert screen.pending["args"]["form_values"] == {"limit": "abc"}
    assert not any(e.get("role") == "user" for e in screen.transcript)

    # enter with a bad integer keeps the form pending, errors stamped
    status = screen.on_key("enter")
    assert "fix the form" in status
    assert screen.pending is not None
    assert screen.pending["args"]["form_errors"]

    # repair the field, launch: card written with typed values
    for ch in "limit=20":
        screen.on_key(ch)
    screen.on_key("enter")
    assert screen.pending["args"].get("form_errors") is None
    status = screen.on_key("enter")
    assert "launch card written" in status, status
    assert screen.pending is None
    card = tmp_path / ".prime-lab" / "launch" / "tester-form.toml"
    data = tomllib.loads(card.read_text())
    assert data["launch"]["kind"] == "eval"
    assert data["eval"]["limit"] == 20 and isinstance(data["eval"]["limit"], int)
    assert data["eval"]["env"] == "gsm8k"
    widget = next(e for e in screen.transcript if e["role"] == "widget")
    assert widget["args"]["saved_card"] == "tester-form.toml"


def test_chat_form_stop_dismisses(tmp_path):
    from prime_tpu.lab.tui.chat import AgentChatScreen
    from prime_tpu.lab.tui.launch import scan_cards

    screen = AgentChatScreen("tester", lambda: None, workspace=str(tmp_path))
    screen.transcript.append(
        {"role": "widget", "name": "configure_run", "args": {"kind": "rl"}}
    )
    screen.pending = screen.transcript[-1]
    for ch in "stop":
        screen.on_key(ch)
    assert screen.on_key("enter") == "form dismissed"
    assert screen.pending is None and scan_cards(tmp_path) == []


def test_chat_form_renders_with_workspace_options(tmp_path):
    import io

    from rich.console import Console

    from prime_tpu.envhub.packaging import write_env_template
    from prime_tpu.lab.tui.chat import AgentChatScreen

    write_env_template(tmp_path / "environments" / "wordle", "wordle")
    screen = AgentChatScreen("tester", lambda: None, workspace=str(tmp_path))
    screen.transcript.append(
        {"role": "widget", "name": "configure_run", "args": {"kind": "eval"}}
    )
    screen.pending = screen.transcript[-1]
    console = Console(width=100, file=io.StringIO(), force_terminal=False)
    console.print(screen.render())
    out = console.file.getvalue()
    assert "Evaluate wordle" in out       # env select seeded from the workspace
    assert "name=value" in out            # edit hint
