"""launch/serve.py driver: the --greedy flag is a real toggle (it used
to be store_true with default=True — dead), timers exclude compile via
warmup, and both the engine and the --static fallback run end-to-end on
the smoke config."""

import numpy as np
import pytest

from repro.launch.serve import build_parser, main, sampling_args


def test_greedy_flag_is_live():
    ap = build_parser()
    assert ap.parse_args(["--arch", "x"]).greedy is True
    assert ap.parse_args(["--arch", "x", "--greedy"]).greedy is True
    # regression: this used to be impossible (flag could not turn off)
    args = ap.parse_args(["--arch", "x", "--no-greedy",
                          "--temperature", "0.7", "--top-k", "5"])
    assert args.greedy is False
    assert sampling_args(args) == {"method": "top_k",
                                   "temperature": 0.7, "top_k": 5}
    args = ap.parse_args(["--arch", "x", "--no-greedy"])
    assert sampling_args(args)["method"] == "temperature"
    assert sampling_args(ap.parse_args(["--arch", "x"]))["method"] \
        == "greedy"


def test_static_path_warmup_and_sampling():
    summary, gen = main([
        "--arch", "qwen2-0.5b", "--smoke", "--static", "--batch", "2",
        "--prompt-len", "8", "--gen", "4"])
    assert summary["mode"] == "static"
    assert summary["sampling"] == "greedy"
    # warmup ran before the timed section, so the timed decode (3 jitted
    # step dispatches) must be far cheaper than the compile it excludes
    assert summary["warmup_s"] > summary["decode_s"]
    assert summary["decode_tok_per_s"] > 0
    assert gen.shape == (2, 4)

    sampled, _ = main([
        "--arch", "qwen2-0.5b", "--smoke", "--static", "--batch", "2",
        "--prompt-len", "8", "--gen", "4", "--no-greedy",
        "--temperature", "1.3"])
    assert sampled["sampling"] == "temperature"


def test_audio_arch_routes_to_static_path():
    """whisper served before the engine existed; the default CLI path
    must keep serving it (auto-routed to the fixed-batch fallback, not
    the engine's NotImplementedError)."""
    summary, gen = main([
        "--arch", "whisper-tiny", "--smoke", "--batch", "2",
        "--prompt-len", "8", "--gen", "4"])
    assert summary["mode"] == "static"
    assert gen.shape == (2, 4)


def test_engine_path_serves_trace():
    summary, done = main([
        "--arch", "qwen2-0.5b", "--smoke", "--requests", "5",
        "--max-slots", "2", "--prompt-len", "12", "--gen", "6",
        "--decode-chunk", "3"])
    assert summary["mode"] == "engine"
    assert summary["requests"] == 5
    assert len(done) == 5
    budgets = {r: len(f.tokens) for r, f in done.items()}
    assert all(1 <= n <= 6 for n in budgets.values())
    assert summary["generated_tokens"] == sum(budgets.values())
    assert summary["decode_tok_per_s"] > 0


def test_latent_attention_arch_is_refused():
    """Serving MLA needs a latent cache the KV pools do not have: the CLI
    says so before it builds anything."""
    with pytest.raises(NotImplementedError, match="latent cache"):
        main(["--arch", "moonlight-16b-a3b", "--smoke", "--batch", "2",
              "--prompt-len", "8", "--gen", "4"])
