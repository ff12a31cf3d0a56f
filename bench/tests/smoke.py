"""A cell of the benchmark cut to a size the CPU runs in seconds: the same
files, with the widths, depth, vocabulary, batch and block cap shrunk."""

from __future__ import annotations

import copy

import harness

#: Limits of the comparison at this size on the CPU, set as on the chip
#: (bench/limits) from readings at this size over 6 seeds of the program
#: and 3 of the control and of half of the batch left out. Largest of the
#: program (the two cells' exact-inverse paths): loss 6.8e-4, grad 9.9e-3,
#: factor 5.8e-3, inverse 2.2e-2, change 8.0e-3. Smallest of the control:
#: loss 1.5e-3, grad 1.5e-2, factor 2.3e-2, inverse 0.20, change 1.7e-2;
#: of half of the batch: loss 3.9e-3, grad 0.10, factor 0.24, inverse
#: 1.5, change 0.13. The program's composed-inverse path reads inverse
#: 0.20 and grad 3.1e-2 here.
LIMITS = {"loss": 0.0015, "grad": 0.02, "factor": 0.012, "inverse": 0.06,
          "change": 0.015}

SMOKE = {"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
         "num_attention_heads": 4, "vocab_size": 256}


def smoke_cell(name: str, chips: int = 1) -> harness.Cell:
    cell = copy.deepcopy(harness.load_cell(name, chips=chips))
    c, t = cell.config, cell.traffic
    kv = 4 if c["num_key_value_heads"] == c["num_attention_heads"] else 2
    c.update(SMOKE, num_key_value_heads=kv)
    c["program"].update(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=kv, head_dim=16,
        d_ff=96, vocab=256, attn_chunk=64,
        train_accum=min(c["program"].get("train_accum", 1), 2))
    t.update(batch=4 * cell.chips, seq=32,
             block_size=16 if t["block_size"] < 1024 else 64)
    cell.limits = dict(LIMITS)
    return cell
