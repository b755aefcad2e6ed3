"""The expert-specialized fine-tuning cell, `train-moe-touched`, driven on
the CPU at a tiny size: a sound run is correct, with its comparison
against the plain reference inside every tolerance and no program
compiled in the window; the control and each planted model fault are
not correct; a save's partly dirty leaves are dirty in exactly the
trained experts' share."""
import contextlib
import time

import numpy as np
import pytest

from chipbench import faults_esft, harness
from chipbench.deploy import train_esft
from chipbench.savelog import SaveLog

#: the configuration's DeepSeek-V2 keys cut to a CPU size, with the same
#: 8-chip share (2 of 16 heads, 8 of 64 experts, 1/8 of the widths)
TINY_MODEL = dict(hidden_size=64, intermediate_size=128,
                  moe_intermediate_size=32, kv_lora_rank=32,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  num_hidden_layers=3, vocab_size=128)


def tiny_spec() -> harness.CellSpec:
    spec = harness.cell_spec(harness.load_benchmark(), "train-moe-touched")
    spec.cfg.update(TINY_MODEL)
    spec.cfg["published"] = dict(spec.cfg["published"], vocab_size=1024)
    # an expert slab of a parameter is 64 x 32 x 2 B: 4 chunks
    spec.cfg["deployment"].update(chunk_bytes=1024, global_batch=2,
                                  seq_len=64)
    return spec


#: the cell's tolerances at the tiny size.  Only the loss's differs: over
#: 128 tokens the program reads 1.1e-5 to 2.7e-4 (8 seeds), the control
#: 1.7e-3 to 5.4e-3 (3 seeds); `m` reads 0.011-0.021, `nu` 0.019-0.041,
#: `update` 5e-9 at most, `route` 1.1e-3 at most
TINY_TOLERANCE = dict(train_esft.TOLERANCE, loss=1e-3)


def tiny_run(work_dir, monkeypatch, seed=2**31 + 11, plant=None):
    monkeypatch.setattr(train_esft, "TOLERANCE", TINY_TOLERANCE)
    return harness.run(tiny_spec(), seed, 1.0, False,
                       t_start=time.perf_counter(), require_tpu=False,
                       plant=plant, work_dir=str(work_dir))


def model_check(printed: str):
    """The readings of the run's last `model check:` line."""
    line = [x for x in printed.splitlines()
            if x.startswith("model check: ")][-1]
    pairs = (item.split(" ") for item in
             line[len("model check: "):].split(", "))
    return {k: float(v) for k, v in pairs}


def test_sound_run_is_correct(tmp_path, monkeypatch, capsys):
    out = tiny_run(tmp_path, monkeypatch)
    assert out.result["correct"], out.numbers
    assert out.numbers["traffic_faults"] == 0
    assert out.result["failed"] == 0 and out.result["attempted"] > 2
    read = model_check(capsys.readouterr().out)
    assert all(read[k] <= TINY_TOLERANCE[k] for k in TINY_TOLERANCE), read
    # the window's saves narrow the speculation to the trained slabs
    # (the mix's 2 warm saves leave it wide), and nothing compiles there
    assert " 0 compiles in the window" in out.info[0], out.info[0]


#: each planted fault, and the readings it must put outside tolerance
FAULTS = {"control": ("m", "nu", "update"),
          "unmasked": ("frozen_moved",),
          "no_bias_correction": ("update",),
          "half_batch": ("loss", "m", "nu")}


@pytest.mark.parametrize("mode", sorted(FAULTS))
def test_planted_model_fault_is_not_correct(tmp_path, monkeypatch, capsys,
                                            mode):
    out = tiny_run(tmp_path, monkeypatch, plant=faults_esft.MODES[mode])
    assert not out.result["correct"]
    assert out.numbers["traffic_faults"] >= len(FAULTS[mode])
    read = model_check(capsys.readouterr().out)
    for k in FAULTS[mode]:
        assert read[k] > TINY_TOLERANCE.get(k, 0), (k, read)


def test_window_saves_read_the_trained_share(tmp_path):
    spec = tiny_spec()
    log = SaveLog(spec.cfg["deployment"]["chunk_bytes"], "",
                  lambda _name: contextlib.nullcontext())
    dep = train_esft.Deployment(spec.cfg, spec.mix, 7, str(tmp_path), log,
                                log.annotate)
    dep.setup()
    log.in_window = True
    for _ in range(4):
        dep.round()
    dep.drain()
    dep.release()
    view = harness.View(saves=[r for r in log.window() if r.stats],
                        stalls=[], trace=None, peaks={}, window_s=1.0)
    assert len(view.saves) == 2
    # 2 of 8 held experts train in every expert leaf (parameters and
    # both moments), and whole chunks hold whole expert slabs
    assert harness.metric_reader("partly_dirty_share")(view) == 0.25


def test_file_keys_map_to_the_chip_share():
    arch = train_esft.arch_config(
        harness.cell_spec(harness.load_benchmark(),
                          "train-moe-touched").cfg)
    assert (arch.n_layers, arch.n_heads, arch.vocab) == (5, 2, 12800)
    assert (arch.moe.n_experts, arch.moe.held, arch.moe.top_k) == (64, 8, 6)
    assert (arch.first_dense_ff, arch.moe.shared_width) == (1368, 352)
    assert (arch.d_model, arch.moe.expert_ff, arch.mla.kv_lora_rank) == (
        2048, 1408, 512)


def test_file_value_the_program_lacks_is_refused():
    for key, value in (("q_lora_rank", 1536),
                       ("routed_scaling_factor", 16.0)):
        with pytest.raises(NotImplementedError):
            train_esft.arch_config(dict(tiny_spec().cfg, **{key: value}))
    cfg = dict(tiny_spec().cfg, n_routed_experts=16)
    with pytest.raises(ValueError):
        train_esft.arch_config(cfg)


def test_token_selection_takes_the_most_routed_experts():
    counts = np.array([[5, 9, 9, 1], [0, 0, 3, 2]])
    assert train_esft.esft_token_selection(counts, [1, 2], 2) == {
        "1": [1, 2], "2": [2, 3]}
