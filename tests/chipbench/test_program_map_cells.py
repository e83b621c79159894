"""The program map on a tiny model of each of the benchmark's five
families, and the new metrics on the rehearsal's line (ISSUE 37): every
instruction of the warmed programs gets a component, each family's own
mechanism lands where the table says, with the program's own scopes and
without them (an executable a cache held from before they were added)."""

import importlib
import json
import re

import pytest

from chipbench import run, spec
from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.obs import programs
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.schema import (
    PROGRAM_COMPONENTS,
    validate_event,
)

SERVING = {
    "llama": "qwen2.5-3b-chat-sat",
    "deepseek_v2": "deepseek-v2-ep4-doc-sat",
    "olmo_hybrid": "olmo-hybrid-7b-pp2-gen-sat",
    "xing4": "xing4.0-29b-a4b-pp7-gen-sat",
}
TRAINING = {"bert": "bert-large-ft-s512"}
SCOPES = re.compile(r"(?<=[/(])(serve/(cache_read|cache_write|sample)|"
                    r"train/(loss|optimizer))/")


def _warm(family: str) -> None:
    """The family's tiny programs, registered as the benchmark's run
    registers them."""
    import jax

    if family in SERVING:
        from chipbench.kinds.serve import build_engine

        cfg = spec.load_cell(SERVING[family], True).config
        build = importlib.import_module("chipbench.families." + family).build
        model, params = build(cfg, 0, dtype=cfg["deployment"]["dtype"])
        build_engine(model, params, cfg["deployment"]).warmup()
        return
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.config import (
        TrainConfig,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
        MeshConfig,
        build_mesh,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.train import Trainer

    cfg = spec.load_cell(TRAINING[family], True).config
    tcfg = TrainConfig(dtype=cfg["deployment"]["dtype"], train_batch_size=4,
                       max_seq_length=32, log_every_steps=0)
    build = importlib.import_module("chipbench.families." + family).build
    model, params = build(cfg, 0, attention_impl="xla",
                          dtype=cfg["deployment"]["dtype"])
    trainer = Trainer(tcfg, model, params, build_mesh(
        MeshConfig(dp=-1), devices=jax.devices()[:1]))
    batch = {"input_ids": jnp.ones((4, 32), jnp.int32),
             "attention_mask": jnp.ones((4, 32), jnp.int32),
             "token_type_ids": jnp.zeros((4, 32), jnp.int32),
             "labels": jnp.zeros((4,), jnp.int32)}
    trainer.state, _ = trainer._train_step(trainer.state, batch)
    _warm.keep = trainer        # the registry holds a trainer weakly


def _maps(family: str, tmp_path, monkeypatch) -> list:
    """``[(map with the program's scopes, map without them)]`` of every
    program the family warms."""
    texts = []
    build_map = programs.build_map
    monkeypatch.setattr(programs, "build_map", lambda text, root="": (
        texts.append((text, root)), build_map(text, root))[1])
    out = tmp_path / "telemetry"
    obs.reset(enabled=True)
    try:
        _warm(family)
        obs.configure(out_dir=str(out), enabled=True)
        obs.flush()
        events = [e for _, e, err in obs.iter_events(
            str(out / "events.jsonl")) if err is None]
    finally:
        _warm.keep = None
        obs.reset()
    maps = [e for e in events if e["type"] == "program_map"]
    assert not [e for e in events if e["type"] == "alert"]
    assert len(maps) == len(texts) >= 1
    for m in maps:
        assert validate_event(m) == []
    return [(m, build_map(SCOPES.sub("", text), root))
            for m, (text, root) in zip(maps, texts)]


def _under(m: dict, needle: str) -> dict:
    """Component -> instructions, over the rows whose path holds
    ``needle``."""
    out: dict = {}
    for row in m["ops"].values():
        if row[0] >= 0 and needle in m["scopes"][row[0]][0] + "/":
            out[row[1]] = out.get(row[1], 0) + 1
    return out


@pytest.mark.parametrize("family", list(SERVING) + list(TRAINING))
def test_every_instruction_of_a_familys_programs_gets_its_component(
        family, tmp_path, monkeypatch):
    pairs = _maps(family, tmp_path, monkeypatch)
    want_programs = ({"train_step_impl"} if family in TRAINING
                     else {"prefill_chunk", "decode_step"})
    assert {m["program"] for m, _ in pairs} == want_programs
    for m, bare in pairs:
        rows = m["ops"]
        assert len(rows) > 50
        count: dict = {}
        for row in rows.values():
            assert row[1] in PROGRAM_COMPONENTS
            count[row[1]] = count.get(row[1], 0) + 1
        assert count.get("other", 0) < 0.05 * len(rows), count
        # every module of the model is where the table says
        assert set(_under(m, "/self_attn/")) <= {"mixer"}
        assert set(_under(m, "/mlp/")) <= {"ffn"}
        assert set(_under(m, "/final_ln/")) <= {"head"}
        if family in SERVING:
            assert m["refined_by"] == ["serve/cache_read",
                                       "serve/cache_write", "serve/sample"]
            assert bare["refined_by"] == []
            assert {"mixer", "ffn", "residual", "head", "embed",
                    "cache"} <= set(count)
            # the write-back and the bucket's gather are the engine's
            assert set(_under(m, "serve/cache_write/")) == {"cache"}
            assert set(_under(m, "serve/cache_read/")) <= {"cache"}
            assert set(_under(m, "serve/sample/")) == {"head"}
        else:
            assert m["refined_by"] == ["train/loss", "train/optimizer"]
            assert {"mixer", "ffn", "residual", "head", "embed",
                    "optimizer"} <= set(count)
            assert set(_under(m, "/attention/")) <= {"mixer", "residual"}
            assert set(_under(m, "/Dropout_*/")) == {"residual"}
            assert set(_under(m, "/ffn/")) <= {"ffn", "residual"}
            assert set(_under(m, "/classifier/")) == {"head"}
            assert set(_under(m, "train/optimizer/")) == {"optimizer"}
            passes = {m["scopes"][r[0]][1] for r in rows.values()
                      if r[0] >= 0 and r[1] == "mixer"}
            assert passes == {"fwd", "bwd"}
        if family in ("deepseek_v2", "xing4"):
            # gate, routing and the grouped matmuls
            moe = _under(m, "/moe/")
            assert set(moe) == {"ffn"} and moe["ffn"] >= 10
            assert set(_under(m, "/shared_experts/")) == {"ffn"}
            assert set(_under(m, "/kv_a_ln/")) == {"mixer"}
        if family == "olmo_hybrid":
            # the recurrence and its convolution, loops included
            rec = _under(m, "/linear_attn/")
            assert set(rec) == {"mixer"} and rec["mixer"] >= 20
            if m["program"] == "prefill_chunk":
                assert _under(m, "/linear_attn/while/")
        if family == "xing4":
            # the wrap's maps and mixes, but not what it wraps
            for hc in ("/attn_hc/", "/ffn_hc/"):
                wrap = _under(m, hc)
                assert set(wrap) == {"residual"} and wrap["residual"] >= 20
        # the same answer from module paths and primitives alone: what a
        # cache's older executable carries
        assert set(bare["ops"]) == set(rows)
        moved = [name for name, row in rows.items()
                 if bare["ops"][name][1] != row[1]]
        assert len(moved) <= 0.03 * len(rows), [
            (n, rows[n][1], bare["ops"][n][1]) for n in moved]
        for name, row in rows.items():
            if row[0] >= 0 and "serve/cache_write" in m["scopes"][row[0]][0] \
                    and "scatter" in name:
                assert bare["ops"][name][1] == "cache"


def _rehearsal(capsys, cell: str) -> dict:
    assert run.main(["--workload", cell, "--seconds", "3",
                     "--trace", "1"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell, new", [
    ("qwen2.5-3b-chat-sat", (
        "prefill_mixer_ms", "prefill_ffn_ms", "prefill_residual_ms",
        "prefill_cache_ms", "prefill_head_ms", "scope_coverage_share")),
    ("bert-large-ft-s512", (
        "train_mixer_ms", "train_ffn_ms", "train_residual_ms",
        "train_optimizer_ms", "train_scope_coverage_share")),
])
def test_the_rehearsal_prints_the_new_metrics_and_compiles_nothing_in_its_window(
        capsys, cell, new):
    line = _rehearsal(capsys, cell)
    assert line["correct"] is True
    assert line["check"]["compiles_in_window"] == [0, 0]
    for name in new:
        assert line["metrics"][name]["value"] >= 0.0, name
        assert line["metrics"][name]["unit"] == (
            "%" if "coverage" in name else "ms")
    coverage = line["metrics"][new[-1]]["value"]
    assert coverage > 50.0          # a CPU's threads overlap: no upper end
