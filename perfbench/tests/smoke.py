"""A smoke-size cell for CPU tests: the granite-8b.chat cell's mix and
limits, at widths and lengths a test run can hold."""
import copy
import json
import time

from perfbench import harness


def cell(name: str = "granite-8b.chat", layers: int = 2,
         d_model: int = 64) -> harness.Cell:
    real = harness.load_cell(name)
    cfg = dict(real.cfg, n_layers=layers, d_model=d_model, n_heads=4,
               n_kv_heads=2, d_ff=d_model * 5 // 2, vocab_size=512,
               head_dim=d_model // 4)
    mix = copy.deepcopy(real.mix)
    mix["arrivals"]["rate_per_s"] = 4.0
    mix["prompt_len"] = {"support": [8, 16, 40], "weights": [1, 1, 1]}
    mix["output_len"].update(median=6, min=2, max=12)
    mix["engine"] = {"slots": 4, "max_len": 64}
    mix["drain_cap_s"] = 20
    return harness.Cell(real.name, cfg, mix, 1, real.limits, real.end_to_end,
                        real.per_layer)


def run(c: harness.Cell, seed: int = 2**31 + 7, seconds: float = 3.0,
        traced: bool = False, **kw):
    return harness.run_cell(c, seed, seconds, traced,
                            t_start=time.perf_counter(), require_tpu=False,
                            log=lambda _m: None, **kw)


def last_line(result) -> dict:
    return json.loads(json.dumps(result))
