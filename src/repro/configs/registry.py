"""Architecture registry: the 10 assigned (arch x shape) configs."""

from __future__ import annotations

import importlib

# assignment spellings (CLI: --arch <id>)
ARCHS = (
    "moonlight-16b-a3b",
    "phi3.5-moe-42b-a6.6b",
    "recurrentgemma-9b",
    "qwen2.5-32b",
    "llama3.2-1b",
    "qwen1.5-0.5b",
    "qwen2-0.5b",
    "whisper-tiny",
    "qwen2-vl-7b",
    "falcon-mamba-7b",
)


def _module(name: str):
    norm = name.replace(".", "_").replace("-", "_")
    known = {a.replace(".", "_").replace("-", "_"): a for a in ARCHS}
    if norm not in known:
        raise KeyError(f"unknown arch {name!r}; known: {ARCHS}")
    return importlib.import_module("repro.configs." + norm)


def get_config(name: str):
    return _module(name).config()


def get_smoke_config(name: str):
    return _module(name).smoke_config()
