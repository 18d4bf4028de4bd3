#!/usr/bin/env python3
"""chip_smoke.py — does `prime serve` start, compile and answer on the chip?

Run from the repo root on a machine with one TPU chip:

    python3 chip_smoke.py

It drives the serving path once, through the entry points a user would call,
at the full width of qwen3-4b (36 layers, random bf16 weights from the seed,
byte tokenizer, no network):

1. ``kernels`` — a child process runs every Pallas kernel the TPU path can
   dispatch, plus the prefix-page gather, once at the served shape against
   its XLA reference, and reports the device JAX found.
2. ``serve`` — a child process is the real server
   (``python -m prime_tpu.commands.main serve -m qwen3-4b --continuous
   --warmup ...``); this process talks to it over HTTP only: one short chat,
   one SSE-streamed chat, a burst of eight concurrent chats with distinct
   600-1500 byte prompts, then two of those prompts twice more (prefix hits).
   It then reads /metrics and each request's flight-recorder timeline, sends
   SIGTERM and expects a clean exit.

One process per chip: THIS process never imports JAX; each phase is a child
that has exited before the next starts. The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every phase
passed on a TPU. Anything else — no accelerator, a failed request, a failed
phase, PRIME_TPU_PALLAS_INTERPRET set, the deadline — exits non-zero and
prints no such line.

``--rehearsal`` is the only way to run it without a chip: tiny-test on the
CPU with the kernels in interpret mode. It checks this script's own plumbing,
prints REHEARSAL, and never prints the pass line.

Times printed here are set-up facts from one run (start-up, warm-up/compile,
request wall time), not metrics.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import uuid

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# the whole script, compilation included, must finish inside the driver's
# 1200 s; past this the children are stopped and the run fails
DEADLINE_S = 1150.0

MODEL = "qwen3-4b"
# Four slots, not the serve default of eight, and the reason is the clock, not
# the memory: 8 x 2048 fits one v5e (peak 14.46 GB of a 16.9 GB limit) and this
# script passed with SLOTS = 8 from an empty compile cache in 902 s, against
# 736-766 s with four (PR 21 chip runs; the eight-slot warmup compiles 138
# programs, nine more of the slow `finalize` family). 902 s leaves too little
# of the 1200 s this script is allowed. No width of the model is cut.
SLOTS = 4
SLOT_CAPACITY = 2048
MAX_TOKENS = 64
BURST = 8


class SmokeFailure(Exception):
    """A phase failed; the message says which check."""


def _log(message: str) -> None:
    print(message, flush=True)


# -----------------------------------------------------------------------------
# phase 1 (child): kernels against their XLA references
# -----------------------------------------------------------------------------


def _kernel_child(rehearsal: bool) -> int:
    """Runs in its OWN process (``--child-kernels``): the only code in this
    file that imports JAX. Prints ``DEVICE {json}`` first, then one
    ``KERNEL name err=... tol=... ok`` line per check; exit code 0 only if
    every check passed."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    device = jax.devices()[0]
    print(
        "DEVICE "
        + json.dumps(
            {
                "platform": device.platform,
                "kind": device.device_kind,
                "count": len(jax.devices()),
            }
        ),
        flush=True,
    )
    if device.platform != "tpu" and not rehearsal:
        print(f"no accelerator: JAX found {device.platform}", flush=True)
        return 3

    from prime_tpu.models.llama import quantize_kv
    from prime_tpu.models.quantize import (
        _unpack_nibbles,
        quantize_kv_int4,
        quantize_weight_int4,
    )
    from prime_tpu.ops.attention import (
        decode_attention,
        multi_head_attention,
        xla_attention_causal,
    )
    from prime_tpu.ops.pallas_lora import fused_lora_matmul
    from prime_tpu.ops.pallas_quant import int4_matmul
    from prime_tpu.serve.kv_pool import gather_pages

    # served shapes (qwen3-4b: 32 query / 8 kv heads of 128, 2048-slot cache,
    # hidden 2560, FFN 9728); the rehearsal shrinks them because interpret
    # mode walks the grid in Python
    if rehearsal:
        b, h, kh, d, c, s = 2, 4, 2, 128, 256, 128
        d_model, d_ff, layers_r = 256, 512, 2
    else:
        b, h, kh, d, c, s = 8, 32, 8, 128, 2048, 512
        d_model, d_ff, layers_r = 2560, 9728, 36
    # the attention entry points read PRIME_TPU_PALLAS_INTERPRET (the parent
    # sets it for a rehearsal only); the two matmul kernels take it as a flag
    interpret = rehearsal

    # Tolerance. Inputs are bf16 (8 bits of mantissa, 2^-8 = 3.9e-3 relative).
    # Kernel and reference both accumulate in fp32 but round to bf16 at
    # different points (the XLA paths round the softmax weights to bf16 before
    # the value matmul, Mosaic may feed the MXU bf16 passes of fp32 operands)
    # and sum 2048 slots / 2560 channels in different orders. That leaves a
    # few bf16 ulps of the largest output; a wrong block map, mask or scale is
    # an O(1) error. The error is max|kernel - ref| / max|ref|.
    tol = 2e-2
    # the int4 matmul's reference is exact fp32 math at "highest" precision,
    # the kernel's 20 per-group partial products each pass through the MXU
    # at default precision before the fp32 scale-and-sum: the same bf16-ulp
    # argument, one more rounding step
    tol_int4 = 3e-2

    failures = 0

    def check(name: str, got, want, limit: float) -> None:
        nonlocal failures
        got = np.asarray(jnp.asarray(got, jnp.float32))
        want = np.asarray(jnp.asarray(want, jnp.float32))
        finite = bool(np.isfinite(got).all())
        err = float(np.max(np.abs(got - want)) / max(float(np.max(np.abs(want))), 1e-30))
        ok = finite and got.shape == want.shape and err <= limit
        failures += 0 if ok else 1
        print(
            f"KERNEL {name} shape={tuple(got.shape)} err={err:.3e} tol={limit:.0e} "
            f"finite={finite} {'ok' if ok else 'FAILED'}",
            flush=True,
        )

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 32))

    def normal(shape, dtype=jnp.bfloat16):
        return jax.random.normal(next(keys), shape, dtype=jnp.float32).astype(dtype)

    sm_scale = d**-0.5
    # impl="pallas" forces the kernel (auto would also pick it on a TPU at
    # C=2048); impl="xla" is the repo's reference path

    # ragged lengths: an empty-ish row, block edges, a full row
    lengths = jnp.asarray(
        ([1, c] if rehearsal else [1, 17, 128, 129, 1000, 2047, 2048, 555])[:b],
        dtype=jnp.int32,
    )
    q = normal((b, h, 1, d))
    k = normal((b, kh, d, c))
    v = normal((b, kh, d, c))

    check(
        "flash_decode_bf16",
        decode_attention(q, k, v, lengths, sm_scale, impl="pallas"),
        decode_attention(q, k, v, lengths, sm_scale, impl="xla"),
        tol,
    )
    k8, k8s = quantize_kv(k)
    v8, v8s = quantize_kv(v)
    check(
        "flash_decode_int8",
        decode_attention(q, k8, v8, lengths, sm_scale, impl="pallas", k_scale=k8s, v_scale=v8s),
        decode_attention(q, k8, v8, lengths, sm_scale, impl="xla", k_scale=k8s, v_scale=v8s),
        tol,
    )
    k4, k4s = quantize_kv_int4(k)
    v4, v4s = quantize_kv_int4(v)
    check(
        "flash_decode_int4",
        decode_attention(q, k4, v4, lengths, sm_scale, impl="pallas", k_scale=k4s, v_scale=v4s),
        decode_attention(q, k4, v4, lengths, sm_scale, impl="xla", k_scale=k4s, v_scale=v4s),
        tol,
    )
    qp = normal((1, h, s, d))
    kp = normal((1, kh, s, d))
    vp = normal((1, kh, s, d))
    check(
        "flash_prefill",
        multi_head_attention(qp, kp, vp, sm_scale, impl="pallas"),
        xla_attention_causal(qp, kp, vp, sm_scale),
        tol,
    )

    # fused gathered-LoRA against the einsum chain it replaces
    # (models/llama._lora_mm), the chain at "highest" matmul precision
    rank, bank = 16, 4
    x = normal((b, 1, d_model))
    w = normal((d_model, h * d)) * jnp.bfloat16(d_model**-0.5)
    a = normal((bank, d_model, rank)) * jnp.bfloat16(d_model**-0.5)
    bb = normal((bank, rank, h * d)) * jnp.bfloat16(rank**-0.5)
    ids = jnp.arange(b, dtype=jnp.int32) % bank
    with jax.default_matmul_precision("highest"):
        base = jnp.einsum("bsd,do->bso", x, w, preferred_element_type=jnp.float32)
        hid = jnp.einsum("bsd,bdr->bsr", x.astype(jnp.float32), a[ids].astype(jnp.float32))
        delta = jnp.einsum("bsr,bro->bso", hid, bb[ids].astype(jnp.float32))
        lora_ref = base.astype(x.dtype) + delta.astype(x.dtype)
    check(
        "fused_lora_matmul",
        fused_lora_matmul(x, w, a, bb, ids, interpret=interpret),
        lora_ref,
        tol,
    )

    # fused int4 gemv against x @ dequant(packed, scale)
    xw = normal((b, d_model))
    packed, scale = quantize_weight_int4(normal((d_model, d_ff), jnp.float32))
    groups = scale.shape[-3]
    lo, hi = _unpack_nibbles(packed.reshape(groups, -1, d_ff))
    dense = jnp.concatenate([lo, hi], axis=1).astype(jnp.float32) * scale.reshape(
        groups, 1, d_ff
    )
    with jax.default_matmul_precision("highest"):
        int4_ref = xw.astype(jnp.float32) @ dense.reshape(d_model, d_ff)
    check(
        "int4_matmul",
        int4_matmul(xw, packed, scale[..., 0, :].astype(jnp.float32), interpret=interpret),
        int4_ref,
        tol_int4,
    )

    # prefix-page gather (plain XLA since the Pallas gather was removed): it
    # moves bytes, so it must equal a host-side take exactly
    r_dim, page_tokens, pages = layers_r * kh * d, 16, 24 if rehearsal else 96
    pool = normal((pages, r_dim, page_tokens))
    table = np.full(c // page_tokens, -1, dtype=np.int32)
    used = min(len(table) - 2, pages)  # shuffled pages, then an empty tail
    table[:used] = np.random.default_rng(0).permutation(pages)[:used]
    pool_np = np.asarray(pool.astype(jnp.float32))
    want = np.where(
        (table >= 0)[:, None, None], pool_np[np.maximum(table, 0)], 0.0
    ).swapaxes(0, 1).reshape(r_dim, -1)
    check("prefix_page_gather", gather_pages(pool, jnp.asarray(table)), want, 0.0)

    return 1 if failures else 0


# -----------------------------------------------------------------------------
# phases (parent): children and HTTP only
# -----------------------------------------------------------------------------


def _child_env(rehearsal: bool) -> dict[str, str]:
    """Environment for every child: nothing from ~/.prime or .prime/, every
    output under OUT_DIR. JAX_COMPILATION_CACHE_DIR passes through untouched
    (main() imported prime_tpu, which places the cache when it is unset)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(p for p in (ROOT, env.get("PYTHONPATH")) if p)
    env["PRIME_CONFIG_DIR"] = os.path.join(OUT_DIR, "config")
    env["HOME"] = os.path.join(OUT_DIR, "home")
    env["PRIME_DISABLE_VERSION_CHECK"] = "1"
    env["PYTHONUNBUFFERED"] = "1"
    for name in ("PRIME_TPU_KERNEL_CONFIG_DIR", "PRIME_API_KEY", "PRIME_TRACE"):
        env.pop(name, None)
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
        env["PRIME_TPU_PALLAS_INTERPRET"] = "1"
    return env


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for name in os.listdir(path) if not name.startswith("."))
    except OSError:
        return 0


class _Children:
    """Every process this script starts, so that all of them are stopped on
    any exit path."""

    def __init__(self) -> None:
        self.procs: list[tuple[subprocess.Popen, object]] = []  # (process, its log file)

    def spawn(self, argv: list[str], env: dict[str, str], log_path: str) -> subprocess.Popen:
        log = open(log_path, "wb")  # closed with the process (stop_all)
        proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        self.procs.append((proc, log))
        return proc

    def stop_all(self) -> None:
        for proc, log in self.procs:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except OSError:
                    pass
                try:
                    proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    pass
            log.close()


def _tail(path: str, limit: int = 4000) -> str:
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError:
        return ""
    return data[-limit:].decode("utf-8", errors="replace")


def _run_kernels(children: _Children, env: dict[str, str], rehearsal: bool, deadline: float) -> dict:
    log_path = os.path.join(OUT_DIR, "kernels.log")
    argv = [sys.executable, os.path.abspath(__file__), "--child-kernels"]
    if rehearsal:
        argv.append("--rehearsal")
    t0 = time.monotonic()
    proc = children.spawn(argv, env, log_path)
    try:
        code = proc.wait(timeout=max(1.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        raise SmokeFailure("kernels: deadline passed") from None
    text = _tail(log_path, 20000)
    device = None
    for line in text.splitlines():
        if line.startswith("DEVICE "):
            device = json.loads(line[len("DEVICE "):])
        if line.startswith("KERNEL "):
            _log("  " + line)
    if code != 0 or device is None:
        raise SmokeFailure(f"kernels: child exited {code}\n{text[-3000:]}")
    _log(f"  kernels phase wall time (set-up fact): {time.monotonic() - t0:.1f} s")
    return device


def _free_port() -> int:
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        return probe.getsockname()[1]


def _http(port: int, method: str, path: str, body: dict | None = None,
          headers: dict | None = None, timeout: float = 600.0) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = None if body is None else json.dumps(body).encode()
        send_headers = {"Content-Type": "application/json", **(headers or {})}
        conn.request(method, path, body=payload, headers=send_headers)
        response = conn.getresponse()
        return response.status, response.read()
    finally:
        conn.close()


def _traceparent() -> tuple[str, str]:
    trace_id = uuid.uuid4().hex
    return trace_id, f"00-{trace_id}-{uuid.uuid4().hex[:16]}-01"


def _chat(port: int, content: str, max_tokens: int, stream: bool) -> dict:
    """One chat completion over HTTP. Returns what the client saw: status,
    the trace id it sent, and for a stream the event count and terminator."""
    trace_id, header = _traceparent()
    body = {
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens,
        "temperature": 0.0,
        "stream": stream,
    }
    status, raw = _http(
        port, "POST", "/v1/chat/completions", body, {"traceparent": header}
    )
    seen: dict = {"status": status, "trace_id": trace_id, "stream": stream}
    if status != 200:
        seen["error"] = raw[-300:].decode("utf-8", errors="replace")
        return seen
    if stream:
        events = [
            line[len(b"data: "):]
            for line in raw.split(b"\n")
            if line.startswith(b"data: ")
        ]
        seen["done"] = bool(events) and events[-1] == b"[DONE]"
        chunks = [json.loads(e) for e in events if e != b"[DONE]"]
        seen["finish"] = bool(chunks) and chunks[-1]["choices"][0]["finish_reason"] == "stop"
    else:
        reply = json.loads(raw)
        seen["finish"] = reply["choices"][0]["finish_reason"] == "stop"
        seen["done"] = isinstance(reply["choices"][0]["message"]["content"], str)
    return seen


def _prompt(index: int, size: int) -> str:
    """A distinct prompt of exactly ``size`` bytes: the index leads, so no two
    share a 16-token block with each other (a burst of cold admissions)."""
    words = f"request {index:02d} of the chip smoke asks about topic {index * 7919 % 1000}. "
    text = f"[{index:02d}] " + words * (size // len(words) + 1)
    return text[:size]


_METRIC_RE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")


def _scrape(port: int) -> dict[str, float]:
    """Series of GET /metrics?format=prometheus: each labelled series under
    its full ``name{labels}`` text, and every series summed under its bare
    name."""
    status, raw = _http(port, "GET", "/metrics?format=prometheus", timeout=60)
    if status != 200:
        raise SmokeFailure(f"/metrics answered {status}")
    values: dict[str, float] = {}
    for line in raw.decode().splitlines():
        match = _METRIC_RE.match(line)
        if match and not line.startswith("#"):
            name, labels, value = match.groups()
            try:
                number = float(value)
            except ValueError:
                continue
            values[name] = values.get(name, 0.0) + number
            if labels:
                values[name + labels] = number
    return values


def _run_serve(
    children: _Children, env: dict[str, str], serve_args: list[str], deadline: float
) -> None:
    """Start `prime serve --continuous --warmup <serve_args>` as a child,
    send the smoke's traffic, check what came back and what the server
    counted, stop it with SIGTERM. Raises SmokeFailure on any miss."""
    log_path = os.path.join(OUT_DIR, "server.log")
    port = _free_port()
    argv = [
        sys.executable, "-m", "prime_tpu.commands.main", "serve",
        "--continuous", "--warmup", "--port", str(port), *serve_args,
    ]
    _log("  " + " ".join(argv[1:]))
    t_start = time.monotonic()
    server = children.spawn(argv, env, log_path)

    # The socket is bound at once but nothing answers until the model is
    # built and the warmup has compiled every program. So ONE /healthz request
    # waits in the listen backlog for that moment (polling would leave a
    # trail of abandoned connections for the server to trip over), while this
    # loop watches the child and the clock.
    answer: list = []

    def ask() -> None:
        try:
            answer.append(_http(port, "GET", "/healthz", timeout=DEADLINE_S)[0])
        except (OSError, http.client.HTTPException) as e:
            answer.append(e)

    while True:
        waiter = threading.Thread(target=ask, daemon=True)
        waiter.start()
        while waiter.is_alive():
            if server.poll() is not None:
                raise SmokeFailure(
                    f"serve: server exited {server.returncode} before it was ready\n"
                    + _tail(log_path)
                )
            if time.monotonic() > deadline:
                raise SmokeFailure("serve: deadline passed before /healthz\n" + _tail(log_path))
            waiter.join(timeout=0.5)
        if answer.pop() == 200:
            break
        time.sleep(1.0)  # refused (not bound yet) or 503: ask again
    startup_s = time.monotonic() - t_start
    metrics = _scrape(port)
    _log(
        f"  start-up to /healthz 200 (set-up fact): {startup_s:.1f} s, of which "
        f"warmup/compile {metrics.get('serve_warmup_seconds', 0.0):.1f} s for "
        f"{int(metrics.get('serve_warmup_programs', 0))} programs"
    )
    families = {
        key[key.index('"') + 1 : key.rindex('"')]: value
        for key, value in metrics.items()
        if key.startswith("serve_warmup_program_seconds_sum{")
    }
    _log(
        "  warmup seconds by program family (set-up fact): "
        + ", ".join(f"{name} {secs:.1f}" for name, secs in sorted(families.items()))
    )
    if not metrics.get("serve_warmup_programs"):
        raise SmokeFailure("serve: warmup did not run (serve_warmup_programs is 0)")

    # traffic
    sent: list[dict] = []
    t_req = time.monotonic()
    sent.append(_chat(port, "Say hello to the chip.", 16, stream=False))
    sent.append(_chat(port, "Stream a few tokens, please.", 16, stream=True))
    sizes = [600 + (i * (1500 - 600)) // (BURST - 1) for i in range(BURST)]
    prompts = [_prompt(i, size) for i, size in enumerate(sizes)]
    burst: list[dict | None] = [None] * BURST

    def fire(i: int) -> None:
        try:
            burst[i] = _chat(port, prompts[i], MAX_TOKENS, stream=bool(i % 2))
        except Exception as e:  # noqa: BLE001 — reported as a failed request
            burst[i] = {"status": 0, "error": repr(e), "trace_id": "", "stream": bool(i % 2)}

    threads = [threading.Thread(target=fire, args=(i,)) for i in range(BURST)]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(timeout=max(1.0, deadline - time.monotonic()))
        if thread.is_alive():
            raise SmokeFailure("serve: a burst request outlived the deadline")
    sent.extend(r for r in burst if r is not None)
    # two of the burst's prompts again, each twice: batched admission stores
    # only the first member of a wave, so the first re-send may be the one
    # that stores the prompt; the second must hit and seed from the page pool
    for i in (0, 1, 0, 1):
        sent.append(_chat(port, prompts[i], MAX_TOKENS, stream=False))
    _log(
        f"  {len(sent)} requests, wall time (set-up fact): "
        f"{time.monotonic() - t_req:.1f} s"
    )

    bad = [r for r in sent if r["status"] != 200 or not r.get("done") or not r.get("finish")]
    if bad or len(sent) != 2 + BURST + 4:
        raise SmokeFailure(f"serve: {len(bad)} of {len(sent)} requests failed: {bad[:3]}")

    # what the server says it did: per request (flight recorder, by the trace
    # id this client chose) and in total (/metrics)
    tokens_received = 0
    for request in sent:
        status, raw = _http(port, "GET", f"/debug/requests/{request['trace_id']}", timeout=60)
        if status != 200:
            raise SmokeFailure(f"serve: no flight record for {request['trace_id']}")
        timeline = json.loads(raw)
        final = timeline["events"][-1] if timeline.get("events") else {}
        tokens = int(final.get("tokens", 0))
        if timeline.get("outcome") != "completed" or tokens < 1:
            raise SmokeFailure(f"serve: request did not complete with a token: {timeline}")
        tokens_received += tokens
    metrics = _scrape(port)
    source = {0: "default", 1: "tuned", 2: "env"}.get(
        int(metrics.get("serve_kernel_config_source", -1)), "unknown"
    )
    _log(
        "  /metrics: completed={:.0f} failed={:.0f} tokens_emitted={:.0f} "
        "(flight records: {}) prefix_hits={:.0f} paged_seeds={:.0f} "
        "kernel_config_source={}".format(
            metrics.get("serve_requests_completed_total", -1),
            metrics.get("serve_requests_failed_total", -1),
            metrics.get("serve_tokens_emitted_total", -1),
            tokens_received,
            metrics.get("serve_prefix_hits_total", -1),
            metrics.get("serve_prefix_paged_seeds_total", -1),
            source,
        )
    )
    problems = []
    if metrics.get("serve_requests_failed_total") != 0:
        problems.append("serve_requests_failed_total != 0")
    if metrics.get("serve_requests_completed_total") != len(sent):
        problems.append("serve_requests_completed_total != requests sent")
    if metrics.get("serve_prefix_hits_total", 0) < 2:
        problems.append("serve_prefix_hits_total < 2")
    if metrics.get("serve_tokens_emitted_total") != tokens_received:
        problems.append("tokens emitted != tokens received")
    if source != "default":
        problems.append(f"kernel config source is {source}: an untracked artifact or a knob")
    if problems:
        raise SmokeFailure("serve: " + "; ".join(problems) + "\n" + _tail(log_path))

    # clean exit on SIGTERM
    server.send_signal(signal.SIGTERM)
    try:
        code = server.wait(timeout=max(1.0, min(120.0, deadline - time.monotonic())))
    except subprocess.TimeoutExpired:
        raise SmokeFailure("serve: server did not exit on SIGTERM") from None
    text = _tail(log_path, 200000)
    if code != 0:
        raise SmokeFailure(f"serve: server exited {code} on SIGTERM\n{text[-3000:]}")
    for marker in ("Traceback", "warmup failed", "prefill failed", "decode failed"):
        if marker in text:
            raise SmokeFailure(f"serve: server log contains {marker!r}\n{text[-3000:]}")
    for line in text.splitlines():
        if "memory at" in line:
            _log("  server log:" + line.rstrip())


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearsal", action="store_true",
        help="tiny-test on the CPU with interpret-mode kernels; never prints the pass line",
    )
    parser.add_argument("--child-kernels", action="store_true", help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.child_kernels:
        return _kernel_child(args.rehearsal)

    if not os.path.isdir(os.path.join(ROOT, "prime_tpu")):
        _log("chip_smoke: no prime_tpu package beside this script; nothing to smoke")
        return 2
    if os.environ.get("PRIME_TPU_PALLAS_INTERPRET") and not args.rehearsal:
        _log("chip_smoke: PRIME_TPU_PALLAS_INTERPRET is set; the smoke runs compiled kernels only")
        return 2

    os.makedirs(os.path.join(OUT_DIR, "config"), exist_ok=True)
    os.makedirs(os.path.join(OUT_DIR, "home"), exist_ok=True)
    # importing the package (no JAX) places JAX_COMPILATION_CACHE_DIR when it
    # is unset; every child inherits it
    import prime_tpu  # noqa: F401

    env = _child_env(args.rehearsal)
    cache = env["JAX_COMPILATION_CACHE_DIR"]
    entries_before = _cache_entries(cache)
    deadline = time.monotonic() + DEADLINE_S
    if args.rehearsal:
        _log("REHEARSAL: tiny-test on the CPU, interpret-mode kernels; proves nothing about the chip")
    _log(f"compile cache: {cache} ({entries_before} entries before)")

    children = _Children()
    device: dict | None = None
    try:
        _log("phase kernels: each TPU-path kernel against its XLA reference")
        device = _run_kernels(children, env, args.rehearsal, deadline)
        _log(f"  device: {json.dumps(device)}")
        model = "tiny-test" if args.rehearsal else MODEL
        _log(f"phase serve: {model}, {SLOTS} slots x {SLOT_CAPACITY}")
        _run_serve(
            children, env,
            ["-m", model, "--slots", str(SLOTS), "--slot-capacity", str(SLOT_CAPACITY)],
            deadline,
        )
    except SmokeFailure as failure:
        _log(f"FAILED {failure}")
        return 1
    finally:
        children.stop_all()
    _log(f"compile cache: {cache} ({_cache_entries(cache)} entries after)")

    if args.rehearsal:
        _log("REHEARSAL passed (no result line: this was not a chip run)")
        return 0
    if device["platform"] != "tpu":
        _log(f"FAILED device is {device['platform']}, not a TPU")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
