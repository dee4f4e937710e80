"""The benchmark's family seam and its listing, where the driver counts them:
the checks of ``perfbench/tests/test_families.py`` as cases over every family,
every kernel's operation counts against a hand count written here, the
listing walked a case an ``(entry, cell)`` and a ``(metric file, cell)``, and
the cells' toy twins rehearsed to their end. A family adds rows to the
tables; nothing here names a metric, a suffix or a count of the listing."""

import json
import os
import subprocess
import sys
import types

import pytest

from perfbench_listing import (BENCH, CELLS, FIELDS, FILE_PAIRS, LISTED,
                               PAIRS, ROOT, SPECS, counts, files_reading,
                               kernel_of, load, named, reports)
from perfbench import families, flops, readers, run

CONFIGS = {"cgpt-1p3b": "gpt2", "cgpt-1p3b-d20": "gpt2",
           "laguna-xs2-share8": "laguna", "mellum2-12b-d8": "mellum",
           "jamba2-3b": "jamba", "lfm2-24b-a2b-d9": "lfm2",
           "keye-vl2-30b-a3b-stage0": "keye",
           "dots-vlm1-share32-d6": "dotsvlm",
           "qwen3-next-80b-a3b-ep4-d8": "qwen3next"}
JOBS = {"gpt2": "pretrain_1chip", "laguna": "laguna_pretrain_8k",
        "mellum": "mellum_code_16k", "jamba": "jamba_reasoning_6k",
        "lfm2": "lfm2_agents_3k", "keye": "keye_longdoc_24k",
        "dotsvlm": "dotsvlm_docs_16k", "qwen3next": "qwen3next_docs_8k"}
FAMILY_CONFIG = {"gpt2": "cgpt-1p3b-d20", "laguna": "laguna-xs2-share8",
                 "mellum": "mellum2-12b-d8", "jamba": "jamba2-3b",
                 "lfm2": "lfm2-24b-a2b-d9",
                 "keye": "keye-vl2-30b-a3b-stage0",
                 "dotsvlm": "dots-vlm1-share32-d6",
                 "qwen3next": "qwen3-next-80b-a3b-ep4-d8"}
RATE = {"gpt2": "train_tok_s_chip", "laguna": "train_tok_s_chip",
        "mellum": "serve_tok_s", "jamba": "serve_tok_s",
        "lfm2": "serve_tok_s", "keye": "serve_tok_s",
        "dotsvlm": "serve_tok_s", "qwen3next": "serve_tok_s"}


def config(name):
    return load("configs", name + ".json")


def test_an_unknown_family_is_an_error_that_lists_the_known_ones():
    with pytest.raises(SystemExit) as e:
        families.load({"name": "some-model", "family": "no_such_family"})
    assert "no_such_family" in str(e.value)
    assert families.known() == ["dotsvlm", "gpt2", "jamba", "keye", "laguna",
                                "lfm2", "mellum", "qwen3next"]
    assert all(name in str(e.value) for name in families.known())


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_a_configuration_reaches_its_family_with_every_export(name):
    cfg = config(name)
    assert families.name_of(cfg) == CONFIGS[name]
    family = families.load(cfg)
    assert family.__name__ == "perfbench.families." + CONFIGS[name]
    assert all(callable(getattr(family, x)) for x in families.EXPORTS)


def test_a_family_that_lacks_an_export_is_refused(monkeypatch):
    half = types.ModuleType("perfbench.families.fixture_half")
    half.forward = lambda params, ids, cfg: None
    monkeypatch.setitem(sys.modules, half.__name__, half)
    with pytest.raises(SystemExit) as e:
        families.load({"name": "x", "family": "fixture_half"})
    assert "train_flops_per_token" in str(e.value)


@pytest.mark.parametrize("name,key", [("cgpt-1p3b-d20", "n_head"),
                                      ("laguna-xs2-share8", "vocab_size"),
                                      ("mellum2-12b-d8", "hidden_size")])
def test_the_file_is_the_truth_the_program_is_checked_against(name, key):
    cfg = config(name)
    family = families.load(cfg)
    family.model_config(cfg)                       # as written: accepted
    cfg[key] += 1
    with pytest.raises(SystemExit):
        family.model_config(cfg)


def test_the_laguna_file_holds_the_published_widths_uncut():
    cfg = config("laguna-xs2-share8")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-XS.2") \
            if os.path.exists(f.name) else None
    if row is None:
        pytest.skip("no catalog here")
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers", "vocab_size",
                       "max_position_embeddings"}
    assert changed <= set(cfg["reduced"])
    mc = families.load(cfg).model_config(cfg)
    assert mc.num_params() == cfg["params_held"] == 975_874_048
    assert (mc.experts, mc.kv_heads, mc.vocab) == \
        ((0, 32), (0, 1), (0, 12544))


def test_the_mellum_file_holds_the_published_widths_uncut():
    cfg = config("mellum2-12b-d8")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):     # the catalog's row, where there is one
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert changed == {"num_hidden_layers", "max_position_embeddings"}
        assert cfg["source"].startswith(row["source_url"])
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) == \
        {"num_hidden_layers", "max_position_embeddings"}
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["num_experts"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["sliding_window"], cfg["vocab_size"]) == \
        (2304, 32, 4, 128, 64, 896, 8, 1024, 98304)
    assert cfg["num_hidden_layers"] >= 8 and cfg["num_hidden_layers"] % 4 == 0
    assert all(cfg.get(k) for k in ("assumed", "published", "deployment",
                                    "engine_why"))
    mc = families.load(cfg).model_config(cfg)
    # 21.2M a layer outside the experts, 6.19M an expert, 453M in embedding
    # and head (and the final norm's 2304)
    layer = 2304 * (32 + 8) * 128 + 32 * 128 * 2304 + 2 * 2304 \
        + 2304 * 64 + 64 * 3 * 2304 * 896
    assert layer == 417_747_456
    assert mc.num_params() == cfg["params_held"] \
        == 8 * layer + 2 * 98304 * 2304 + 2304 == 3_794_966_784
    assert (mc.attention_gate, mc.router_score, mc.dtype) == \
        (False, "softmax", "bfloat16")
    assert mc.shared_expert_intermediate_size == 0
    assert [(n, len(l), w) for n, l, w in mc.cache_kinds()] == \
        [("full_attention", 2, 0), ("sliding_attention", 6, 1024)]


def test_the_mellum_family_refuses_a_training_job_by_name():
    cfg = config("mellum2-12b-d8")
    with pytest.raises(SystemExit) as e:
        families.load(cfg).train_job(cfg, {"kind": "train"})
    assert "no training job" in str(e.value)


def test_any_16_requests_of_mellum_code_16k_fit_the_pool():
    """The file is held to what its ``lengths_why`` says: the largest pair
    sixteen times over fits the full layers' pool, every prompt reaches a
    bucket, and the window layers' pools hold 16 x (window + a block)."""
    from perfbench import traffic as T
    cfg, tr = config("mellum2-12b-d8"), load("traffic",
                                             "mellum_code_16k.json")
    e = cfg["engine"]
    pairs = T.multiset(tr)
    assert len(pairs) == 16 == e["max_slots"]
    assert tr["queue_depth_slots"] == 1 and tr["preroll_completions"] == 16
    assert all(2048 <= p <= 12288 and 256 <= a <= 768 for p, a in pairs)
    assert all(p + a <= tr["multiset"]["max_total"] == e["max_len"]
               for p, a in pairs)
    need = max(-(-(p + a) // e["block_size"]) for p, a in pairs)
    assert need == 49 and 16 * need <= e["num_blocks"] - 1
    assert T.buckets_used(tr, e["buckets"]) == [3072, 6144, 9216, 12288]
    assert all(b % 3072 == 0 and b % 512 == 0 for b in e["buckets"])
    assert e["prefix_cache"] is False


def test_the_jamba_file_holds_the_published_model_uncut():
    cfg = config("jamba2-3b")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):     # the catalog's row, where there is one
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "AI21-Jamba2-3B")
        changed = {k for k, v in row["config"].items() if cfg.get(k, 0) != v}
        assert changed == {"max_position_embeddings"}
        assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == list(cfg["reduced_why"]) == \
        ["max_position_embeddings"]
    assert cfg["published"]["max_position_embeddings"] == 262144
    assert (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["mamba_d_state"],
            cfg["mamba_d_conv"], cfg["mamba_expand"], cfg["mamba_dt_rank"],
            cfg["attn_layer_period"], cfg["attn_layer_offset"],
            cfg["vocab_size"], cfg["tie_word_embeddings"]) == \
        (28, 2560, 8192, 20, 1, 16, 4, 2, 160, 14, 7, 65536, True)
    assert all(cfg.get(k) for k in ("assumed", "published", "deployment",
                                    "engine_why"))
    mc = families.load(cfg).model_config(cfg)
    mamba = 2560 * 10240 + 5120 * 2560 + 5120 * 192 + 160 * 5120 + 5120 \
        + 5120 * 4 + 5120 + 5120 * 16 + 5120 + 160 + 16 + 16
    attn = 2560 * 22 * 128 + 2560 * 2560
    mlp = 3 * 2560 * 8192
    assert (mamba, attn, mlp) == (41_241_792, 13_762_560, 62_914_560)
    assert mc.num_params() == cfg["params_held"] == \
        26 * (mamba + mlp + 5120) + 2 * (attn + mlp + 5120) \
        + 65536 * 2560 + 2560 == 3_029_337_472
    assert (mc.head_dim, mc.dtype, mc.d_inner) == (128, "bfloat16", 5120)
    assert mc.layers_of("attention") == (7, 21)
    # the deployment's bytes: a request's recurrent state, the pool
    state = 26 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert state == 9_318_400 and 64 * state == 596_377_600
    e = cfg["engine"]
    assert e["num_blocks"] * e["block_size"] * 2 * 2 * 128 * 2 == 402_915_328


def test_the_jamba_family_refuses_a_training_job_by_name():
    cfg = config("jamba2-3b")
    with pytest.raises(SystemExit) as e:
        families.load(cfg).train_job(cfg, {"kind": "train"})
    assert "no training job" in str(e.value)


def test_any_64_requests_of_jamba_reasoning_6k_fit_the_pool():
    """The file is held to what its ``lengths_why`` says: the largest pair
    sixty-four times over fits the attention layers' pool, and every
    prompt reaches a bucket whose dispatch the model's budget sizes."""
    from perfbench import traffic as T
    cfg, tr = config("jamba2-3b"), load("traffic", "jamba_reasoning_6k.json")
    e = cfg["engine"]
    pairs = T.multiset(tr)
    assert len(pairs) == 32 and e["max_slots"] == 64
    assert tr["queue_depth_slots"] == 1 and tr["preroll_completions"] == 64
    assert all(256 <= p <= 4096 and 512 <= a <= 2048 for p, a in pairs)
    assert (min(p for p, _ in pairs), max(p for p, _ in pairs),
            min(a for _, a in pairs), max(a for _, a in pairs)) == \
        (267, 3922, 523, 2004)
    assert all(p + a <= tr["multiset"]["max_total"] <= e["max_len"]
               for p, a in pairs)
    # the largest prompt with the largest answer, whatever the pairing
    need = -(-(3922 + 2004) // e["block_size"])
    assert need == 24 and 64 * need == e["num_blocks"] - 1
    assert T.buckets_used(tr, e["buckets"]) == [512, 1024, 2048, 4096]
    assert all(b % 128 == 0 for b in e["buckets"])      # the scan's chunk
    mc = families.load(cfg).model_config(cfg)
    spec_rows = [max(1, min(64, mc.tokens_a_dispatch // b))
                 for b in e["buckets"]]
    assert spec_rows == [2, 1, 1, 1]
    assert e["prefix_cache"] is False


def test_the_lfm2_file_holds_the_published_widths_uncut():
    """Every key of the catalog's ``config`` but the two in ``reduced``
    equals the file's: ``layer_types`` and ``num_dense_layers`` stay as
    published and the cut is read from ``first_layer``."""
    cfg = config("lfm2-24b-a2b-d9")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):     # the catalog's row, where there is one
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-24B-A2B")
        changed = {k for k, v in row["config"].items() if cfg.get(k, 0) != v}
        assert changed == {"num_hidden_layers", "max_position_embeddings"}
        assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == list(cfg["reduced_why"]) == \
        ["num_hidden_layers", "max_position_embeddings"]
    assert cfg["published"]["num_hidden_layers"] == 40
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["conv_L_cache"],
            cfg["vocab_size"], cfg["num_hidden_layers"],
            cfg["first_layer"]) == \
        (2048, 11776, 32, 8, 64, 64, 4, 1536, 3, 65536, 9, 1)
    assert all(cfg.get(k) for k in ("assumed", "published", "deployment",
                                    "engine_why"))
    family = families.load(cfg)
    # published layers 1-9: the second dense layer, then two whole periods
    assert family.layer_plan(cfg) == \
        [("conv", True), ("full_attention", False)] \
        + [("conv", False)] * 3 + [("full_attention", False)] \
        + [("conv", False)] * 3
    mc = family.model_config(cfg)
    experts = 64 * 3 * 2048 * 1536 + 2048 * 64 + 64
    conv = 4 * 2048 * 2048 + 3 * 2048
    attn = 2048 * (32 + 16) * 64 + 32 * 64 * 2048 + 2 * 64
    dense = 3 * 2048 * 11776
    assert (experts, conv, attn, dense) == \
        (604_110_912, 16_783_360, 10_485_888, 72_351_744)
    assert mc.num_params() == cfg["params_held"] == \
        8 * experts + 7 * conv + 2 * attn + dense + 9 * 2 * 2048 \
        + 65536 * 2048 + 2048 == 5_177_950_976
    assert (mc.qk_norm, mc.router_bias, mc.router_score, mc.dtype,
            mc.kv_pack) == (True, True, "sigmoid", "bfloat16", 2)
    # the deployment's bytes: the pool (two heads of 64 in a row of 128),
    # the tails
    e = cfg["engine"]
    spec_pool = e["num_blocks"] * e["block_size"] * 2 * 2 * 4 * 128 * 2
    assert spec_pool == 1_611_661_312
    assert e["max_slots"] * 7 * 2 * 2048 * 2 == 7_340_032


def test_the_lfm2_family_refuses_a_training_job_by_name():
    cfg = config("lfm2-24b-a2b-d9")
    with pytest.raises(SystemExit) as e:
        families.load(cfg).train_job(cfg, {"kind": "train"})
    assert "the lfm2 family has no training job" in str(e.value)


def test_any_128_requests_of_lfm2_agents_3k_fit_the_pool():
    """The file is held to what its ``lengths_why`` says: ``max_total``
    rows 128 times over fit the attention layers' pool, and every prompt
    reaches a bucket whose dispatch computes the model's budget."""
    from perfbench import traffic as T
    cfg, tr = config("lfm2-24b-a2b-d9"), load("traffic",
                                              "lfm2_agents_3k.json")
    e = cfg["engine"]
    pairs = T.multiset(tr)
    assert len(pairs) == 32 and e["max_slots"] == 128
    assert tr["queue_depth_slots"] == 1 and tr["preroll_completions"] == 128
    assert (min(p for p, _ in pairs), max(p for p, _ in pairs),
            min(a for _, a in pairs), max(a for _, a in pairs)) == \
        (134, 1961, 262, 1002)
    assert all(p + a <= tr["multiset"]["max_total"] == 3072 <= e["max_len"]
               for p, a in pairs)
    need = -(-tr["multiset"]["max_total"] // e["block_size"])
    assert need == 12 and 128 * need == e["num_blocks"] - 1
    assert T.buckets_used(tr, e["buckets"]) == [256, 512, 1024, 2048]
    mc = families.load(cfg).model_config(cfg)
    assert [max(1, min(128, mc.tokens_a_dispatch // b))
            for b in e["buckets"]] == [2, 1, 1, 1]
    assert e["prefix_cache"] is False


def test_the_keye_file_holds_the_published_widths_uncut():
    """Every key of the catalog's ``config``, ``sa_config`` and
    ``rope_scaling`` among them, but the two in ``reduced`` equals the
    file's."""
    cfg = config("keye-vl2-30b-a3b-stage0")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):     # the catalog's row, where there is one
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Keye-VL-2.0-30B-A3B")
        changed = {k for k, v in row["config"].items() if cfg.get(k, 0) != v}
        assert changed == {"num_hidden_layers", "max_position_embeddings"}
        assert "sa_config" in row["config"]
        assert cfg["source"].startswith(row["source_url"])
        entry = next(c for c in BENCH["configs"]
                     if c["name"] == cfg["name"])
        assert entry["source"] == row["source_url"]
    assert cfg["reduced"] == list(cfg["reduced_why"]) == \
        ["num_hidden_layers", "max_position_embeddings"]
    assert cfg["published"]["num_hidden_layers"] == 48
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["num_experts"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["vocab_size"], cfg["num_hidden_layers"]) == \
        (2048, 32, 4, 128, 128, 768, 8, 151936, 6)
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert cfg["num_hidden_layers"] >= 4            # the guide's floor
    assert all(cfg.get(k) for k in ("assumed", "published", "deployment",
                                    "engine_why"))
    assert {"qk_norm", "rotary", "indexer", "sa_chunks", "intermediate_size",
            "router", "dtype", "embed_init_std", "indexer_init_std"} \
        <= set(cfg["assumed"])
    mc = families.load(cfg).model_config(cfg)
    # 18.87M attention (+ the norms), the indexer's three projections and
    # its LayerNorm (2.26M, with the q/k norms' gains), 0.26M router,
    # 604.0M experts; 622.3M in embedding and head (and the final norm)
    layer = 2048 * (32 + 8) * 128 + 32 * 128 * 2048 + 2 * 2048 \
        + 2048 * (16 * 64 + 64 + 16) + 2 * 64 + 2 * 128 \
        + 2048 * 128 + 128 * 3 * 2048 * 768
    assert layer == 625_381_760
    assert mc.num_params() == cfg["params_held"] \
        == 6 * layer + 2 * 151936 * 2048 + 2048 == 4_374_622_464
    assert (mc.attention_gate, mc.router_score, mc.qk_norm, mc.dtype,
            mc.kv_pack, mc.indexer) == \
        (False, "softmax", True, "bfloat16", 1, (16, 64, 2048))
    assert mc.shared_expert_intermediate_size == 0
    assert [(n, len(l), w) for n, l, w in mc.cache_kinds()] == \
        [("full_attention", 6, 0)]
    # the deployment's bytes: K and V, and the third array, a token a lane
    e = cfg["engine"]
    assert e["num_blocks"] * 4 * e["block_size"] * 128 * 2 * 2 * 6 \
        == 1_714_421_760
    assert e["num_blocks"] * 64 * e["block_size"] * 2 * 6 == 107_151_360


def test_the_keye_family_refuses_a_training_job_by_name():
    cfg = config("keye-vl2-30b-a3b-stage0")
    with pytest.raises(SystemExit) as e:
        families.load(cfg).train_job(cfg, {"kind": "train"})
    assert "the keye family has no training job" in str(e.value)


def test_any_8_requests_of_keye_longdoc_24k_fit_the_pool():
    """The file is held to what its ``lengths_why`` says: the largest pair
    eight times over fits the pool (one table and allocator for K, V and
    the indexer's keys), every prompt reaches a bucket, every bucket is
    whole passes of the expert layer and whole chunks of the sparse
    attention, and every request is past ``topk`` from its first token
    on."""
    from paddle_tpu.models.keye import PROMPT_CHUNK_ROWS
    from paddle_tpu.ops.attention_ops import SPARSE_QUERY_CHUNK
    from perfbench import traffic as T
    cfg, tr = config("keye-vl2-30b-a3b-stage0"), load(
        "traffic", "keye_longdoc_24k.json")
    e = cfg["engine"]
    pairs = T.multiset(tr)
    assert len(pairs) == 16 and e["max_slots"] == 8
    assert tr["queue_depth_slots"] == 1 and tr["preroll_completions"] == 8
    assert (min(p for p, _ in pairs), max(p for p, _ in pairs),
            min(a for _, a in pairs), max(a for _, a in pairs)) == \
        (4277, 15689, 267, 981)
    assert all(p + a <= tr["multiset"]["max_total"] == e["max_len"] == 17408
               for p, a in pairs)
    assert min(p for p, _ in pairs) > cfg["sa_config"]["topk"]
    need = max(-(-(p + a) // e["block_size"]) for p, a in pairs)
    assert need == 63 and 8 * need <= e["num_blocks"] - 1 == 8 * 68
    assert e["max_len"] == 68 * e["block_size"] \
        == cfg["max_position_embeddings"]
    assert T.buckets_used(tr, e["buckets"]) == e["buckets"] \
        == [8192, 12288, 16384]
    assert len(e["buckets"]) <= 5 and all(
        b % PROMPT_CHUNK_ROWS == 0 and b % SPARSE_QUERY_CHUNK == 0
        for b in e["buckets"])
    assert e["prefix_cache"] is False


def test_the_dotsvlm_file_holds_the_published_widths_uncut():
    """Every key of the catalog's ``config``, ``rope_scaling`` whole, but
    the five in ``reduced`` equals the file's; the share and the bytes are
    what ISSUE 49 reckoned."""
    cfg = config("dots-vlm1-share32-d6")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    reduced = ["num_hidden_layers", "first_k_dense_replace",
               "n_routed_experts", "vocab_size", "max_position_embeddings"]
    if os.path.exists(catalog):     # the catalog's row, where there is one
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "dots.vlm1.inst")
        changed = {k for k, v in row["config"].items() if cfg.get(k, 0) != v}
        assert changed == set(reduced)
        assert cfg["source"].startswith(row["source_url"])
        entry = next(c for c in BENCH["configs"]
                     if c["name"] == cfg["name"])
        assert entry["source"] == row["source_url"]
        assert entry["reduced"] == reduced
    assert cfg["reduced"] == list(cfg["reduced_why"]) == reduced
    assert [cfg["published"][k] for k in reduced] == \
        [61, 3, 256, 129280, 163840]
    assert (cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["num_attention_heads"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"]) == \
        (7168, 1536, 512, 128, 64, 128, 128, 18432, 2048, 8, 4, 8, 2.5)
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    # the share: 8 of 256 experts (the guide's floor), 1/8 of the rows,
    # one dense layer and five expert layers (a period and four)
    family = families.load(cfg)
    assert (cfg["n_routed_experts"], cfg["expert_share"],
            family.router_width(cfg)) == (8, 32, 256)
    assert cfg["vocab_size"] * cfg["vocab_share"] == 129280
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"]) == (6, 1)
    assert all(cfg.get(k) for k in ("assumed", "published", "deployment",
                                    "engine_why"))
    assert {"rotary", "norm_placement", "dtype", "weights", "embed_init_std",
            "router_bias_init_std", "router", "held_experts", "not_built"} \
        <= set(cfg["assumed"])
    mc = family.model_config(cfg)
    attention = 7168 * 1536 + 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 \
        + 512 * 128 * 256 + 128 * 128 * 7168
    expert = 3 * 7168 * 2048
    sparse = attention + 2 * 7168 + 7168 * 256 + 256 + 9 * expert
    dense = attention + 2 * 7168 + 3 * 7168 * 18432
    assert (attention, expert) == (187_107_328, 44_040_192)
    assert mc.num_params() == cfg["params_held"] \
        == dense + 5 * sparse + 2 * 16160 * 7168 + 7168 == 3_741_753_600
    assert (mc.experts, mc.vocab, mc.num_experts, mc.vocab_size) == \
        ((0, 8), (0, 16160), 256, 129280)
    assert (mc.router_score, mc.router_bias, mc.router_groups,
            mc.router_topk_groups, mc.attention_gate, mc.dtype) == \
        ("sigmoid", True, 8, 4, False, "bfloat16")
    assert mc.mlp_layer_types == ("dense",) + ("sparse",) * 5
    # the deployment's bytes: ONE array a layer, a token a lane
    e = cfg["engine"]
    assert e["num_blocks"] * 576 * e["block_size"] * 2 * 6 == 3_852_140_544


def test_any_32_requests_of_dotsvlm_docs_16k_fit_the_pool():
    """The largest pair 32 times over fits the pool, every prompt reaches
    a bucket, and every bucket is whole passes of the expert layer and
    whole query tiles of the prompt's read."""
    from paddle_tpu.models.dotsvlm import PROMPT_CHUNK_ROWS
    from paddle_tpu.ops.pallas.mla_attention import PROMPT_BLOCK_Q
    from perfbench import traffic as T
    cfg, tr = config("dots-vlm1-share32-d6"), load(
        "traffic", "dotsvlm_docs_16k.json")
    e = cfg["engine"]
    pairs = T.multiset(tr)
    assert len(pairs) == 16 and e["max_slots"] == 32
    assert tr["queue_depth_slots"] == 1 and tr["preroll_completions"] == 32
    assert tr["multiset"]["pairing_seed"] == 49
    assert (min(p for p, _ in pairs), max(p for p, _ in pairs),
            min(a for _, a in pairs), max(a for _, a in pairs)) == \
        (4277, 15689, 267, 981)
    assert all(p + a <= tr["multiset"]["max_total"] == e["max_len"] == 17408
               for p, a in pairs)
    need = max(-(-(p + a) // e["block_size"]) for p, a in pairs)
    assert need <= 68 and 32 * 68 == e["num_blocks"] - 1
    assert e["max_len"] == 68 * e["block_size"] \
        == cfg["max_position_embeddings"]
    assert T.buckets_used(tr, e["buckets"]) == e["buckets"] \
        == [6144, 8192, 12288, 16384]
    assert all(b % PROMPT_CHUNK_ROWS == 0 and b % PROMPT_BLOCK_Q == 0
               for b in e["buckets"])
    assert e["prefix_cache"] is False


def test_the_qwen3next_file_holds_the_published_widths_uncut():
    """Every key of the catalog's ``config`` but the four in ``reduced``
    equals the file's; the share and the bytes are what ISSUE 58 reckoned."""
    cfg = config("qwen3-next-80b-a3b-ep4-d8")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    reduced = ["num_hidden_layers", "num_experts", "vocab_size",
               "max_position_embeddings"]
    if os.path.exists(catalog):     # the catalog's row, where there is one
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        changed = {k for k, v in row["config"].items()
                   if cfg.get(k, 0) != v}
        assert changed == set(reduced)
        entry = next(c for c in BENCH["configs"]
                     if c["name"] == cfg["name"])
        assert cfg["source"] == entry["source"] == row["source_url"]
        assert entry["reduced"] == reduced
    assert cfg["reduced"] == list(cfg["reduced_why"]) == reduced
    assert [cfg["published"][k] for k in reduced] == \
        [48, 512, 151936, 262144]
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_num_key_heads"],
            cfg["linear_num_value_heads"], cfg["linear_conv_kernel_dim"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["partial_rotary_factor"],
            cfg["rope_theta"], cfg["full_attention_interval"]) == \
        (2048, 256, 16, 2, 128, 128, 16, 32, 4, 512, 512, 10, 0.25,
         10_000_000, 4)
    # the share: 128 of 512 experts, 1/4 of the rows, two whole periods
    family = families.load(cfg)
    assert (cfg["num_experts"], cfg["expert_share"],
            family.router_width(cfg)) == (128, 4, 512)
    assert cfg["vocab_size"] * cfg["vocab_share"] == 151936
    assert cfg["num_hidden_layers"] == 2 * cfg["full_attention_interval"]
    assert all(cfg.get(k) for k in ("assumed", "published", "deployment",
                                    "engine_why"))
    assert {"column_order", "multi_token_prediction", "state", "dtype",
            "weights", "recurrence_init", "embed_init_std"} \
        <= set(cfg["assumed"])
    mc = family.model_config(cfg)
    linear, full, rest, expert = (33_718_464, 27_263_488,
                                  4_196_352 + 4_096, 3_145_728)
    assert mc.num_params() == cfg["params_held"] \
        == 6 * linear + 2 * full + 8 * (rest + 128 * expert) \
        + 2 * 37984 * 2048 + 2048 == 3_667_251_328
    assert (mc.experts, mc.num_experts, mc.vocab_size) == \
        ((0, 128), 512, 37984)
    assert (mc.router_score, mc.router_bias, mc.attention_gate, mc.dtype,
            mc.moe_routed_scaling_factor) == \
        ("softmax", False, False, "bfloat16", 1.0)
    assert mc.layers_of("full_attention") == (3, 7)
    spec_state = mc.state_arrays()
    assert spec_state == (((3, 8192), "bfloat16"),
                          ((32, 128, 128), "float32"))
    # the deployment's bytes: the pool and the recurrent state
    e = cfg["engine"]
    assert e["num_blocks"] * e["block_size"] * 2 * 2048 == 2_349_858_816
    assert e["max_slots"] * 6 * (32 * 128 * 128 * 4 + 3 * 8192 * 2) \
        == 64 * 6 * 2_146_304 == 824_180_736


def test_the_qwen3next_family_refuses_a_training_job_by_name():
    cfg = config("qwen3-next-80b-a3b-ep4-d8")
    family = families.load(cfg)
    with pytest.raises(SystemExit, match="qwen3next family has no training"):
        family.train_job(cfg, {"kind": "train"})
    with pytest.raises(SystemExit, match="qwen3next family has no loss"):
        family.loss({}, None, None, cfg)


def test_any_64_requests_of_qwen3next_docs_8k_fit_the_pool():
    """The longest prompt with the longest answer 64 times over fits the
    pool, every prompt reaches a bucket, and every bucket is whole chunks
    of the delta rule and whole blocks of the flash forward."""
    from paddle_tpu.ops.pallas.gated_delta import CHUNK
    from perfbench import traffic as T
    cfg, tr = config("qwen3-next-80b-a3b-ep4-d8"), load(
        "traffic", "qwen3next_docs_8k.json")
    e = cfg["engine"]
    pairs = T.multiset(tr)
    assert len(pairs) == 16 and e["max_slots"] == 64
    assert tr["queue_depth_slots"] == 1 and tr["preroll_completions"] == 64
    assert tr["multiset"]["pairing_seed"] == 58
    assert (min(p for p, _ in pairs), max(p for p, _ in pairs),
            min(a for _, a in pairs), max(a for _, a in pairs)) == \
        (2139, 7845, 267, 981)
    assert all(p + a <= tr["multiset"]["max_total"] == e["max_len"] == 9216
               for p, a in pairs)
    need = -(-(7845 + 981) // e["block_size"])
    assert need == 35 and 64 * need == e["num_blocks"] - 1
    assert e["max_len"] == 36 * e["block_size"] \
        == cfg["max_position_embeddings"]
    assert T.buckets_used(tr, e["buckets"]) == e["buckets"] \
        == [3072, 4096, 6144, 8192]
    assert [sum(T.bucket_for(p, e["buckets"]) == b for p, _ in pairs)
            for b in e["buckets"]] == [5, 3, 5, 3]
    assert all(b % CHUNK == 0 and b % 512 == 0 for b in e["buckets"])
    # one prompt a dispatch, whatever the bucket
    assert all(cfg["tokens_a_dispatch"] // b <= 1 for b in e["buckets"])
    assert e["prefix_cache"] is False


# per layer 8*2048^2 + 4*2048*8192 + 2*1024*2048 = 104,857,600; head
# 2*2048*50304 = 206,045,184; x3 for the backward.
# Laguna share, forward a token at s 8192: a window layer's projections
# 2*2048*(8+2)*128 + 2*2048*8 + 2*8*128*2048 = 9,469,952 and scores
# 4*8*128*496.03125 = 2,031,744 (mean keys (512*513/2 + 7680*512) / 8192);
# a full layer's 2*2048*8*128 + 2*2048*6 + 2*6*128*2048 = 7,364,608 and
# 4*6*128*4096.5 = 12,584,448; the dense MLP 6*2048*8192 = 100,663,296; a
# sparse MLP 2*2048*256 + 6*2048*512 (shared) + 8*32/256 * 6*2048*512
# (routed) = 13,631,488; the head 2*2048*12544 = 51,380,224. Layers 0-8 are
# 3 full, 6 window; 1 dense, 8 sparse.
LAGUNA_FORWARD = (3 * (7_364_608 + 12_584_448) + 6 * (9_469_952 + 2_031_744)
                  + 100_663_296 + 8 * 13_631_488 + 51_380_224)


@pytest.mark.parametrize("name,seq,by_hand", [
    ("cgpt-1p3b", 1024, 3 * (24 * 104_857_600 + 206_045_184)),
    ("cgpt-1p3b-d20", 1024, 3 * (20 * 104_857_600 + 206_045_184)),
    ("laguna-xs2-share8", 8192, 3 * LAGUNA_FORWARD),
    # Qwen3-Next's share, forward a token at s 4096: a DeltaNet mixer
    # 2*2048*12352 + 2*4096*2048 + 8*32*128*128 = 71,565,312; an attention
    # mixer 2*2048*36*256 + 2*4096*2048 + 4*16*256*2048.5 = 88,088,576; an
    # expert layer 2*2048*512 + 2*2048 + 6*2048*512 (shared) + 2.5 *
    # 6*2048*512 (routed, held) = 24,121,344; the head 2*2048*37984
    ("qwen3-next-80b-a3b-ep4-d8", 4096,
     3 * (6 * 71_565_312 + 2 * 88_088_576 + 8 * 24_121_344
          + 155_582_464))])
def test_train_flops_per_token_is_the_hand_count(name, seq, by_hand):
    cfg = config(name)
    assert families.load(cfg).train_flops_per_token(cfg, seq) == by_hand
    if name == "laguna-xs2-share8":
        assert LAGUNA_FORWARD == 389_952_768


# one call's (FLOPs, bytes), by hand. gpt2 at batch 8 x 16 heads, s 1024,
# d 128: a matmul is 2*128*1024^2*128/2; an array 128*1024*128*2 bytes, a
# float32 row 128*1024*4. Laguna at batch 2, s 8192, d 128, 1 KV head:
# window layers 8 query heads (bh 16, 496.03125 keys), full layers 6 (bh
# 12, 4096.5 keys); an array of the query heads is bh*8192*128*2 bytes, of
# the KV head 2*8192*128*2; matmuls 2 / 3 / 4, query-head arrays 2 / 3 / 2,
# KV arrays 2 / 2 / 4, rows 1 / 2 / 2. Grouped products at the expected load
# under uniform ids, 16384 tokens * 8 choices * 32/256 held = 16384 rows:
# 2*16384*2048*n FLOPs, n 1024 (gate and up) or 512 (down), and the rows on
# both sides of the product plus the held stack [32, 2048, n] at 2 bytes.
MM = 2 * 128 * 1024 * 1024 * 128 // 2
ARR, ROW = 128 * 1024 * 128 * 2, 128 * 1024 * 4
QW, QF, KV = 16 * 8192 * 128 * 2, 12 * 8192 * 128 * 2, 2 * 8192 * 128 * 2
UP = (68_719_476_736, 2 * 16384 * (2048 + 1024) + 2 * 32 * 2048 * 1024)
DOWN = (34_359_738_368, 2 * 16384 * (512 + 2048) + 2 * 32 * 512 * 2048)
KERNELS = {
    ("gpt2", "flash_fwd"): (2 * MM, 4 * ARR + ROW),
    ("gpt2", "flash_bwd_dq"): (3 * MM, 5 * ARR + 2 * ROW),
    ("gpt2", "flash_bwd_dkv"): (4 * MM, 6 * ARR + 2 * ROW),
    ("laguna", "flash_fwd_win"): (33_288_093_696, 2 * QW + 2 * KV
                                  + 16 * 8192 * 4),
    ("laguna", "flash_fwd_full"): (206_183_596_032, 2 * QF + 2 * KV
                                   + 12 * 8192 * 4),
    ("laguna", "flash_bwd_dq_win"): (49_932_140_544, 3 * QW + 2 * KV
                                     + 2 * 16 * 8192 * 4),
    ("laguna", "flash_bwd_dq_full"): (309_275_394_048, 3 * QF + 2 * KV
                                      + 2 * 12 * 8192 * 4),
    ("laguna", "flash_bwd_dkv_win"): (66_576_187_392, 2 * QW + 4 * KV
                                      + 2 * 16 * 8192 * 4),
    ("laguna", "flash_bwd_dkv_full"): (412_367_192_064, 2 * QF + 4 * KV
                                       + 2 * 12 * 8192 * 4),
    ("laguna", "moe_up"): UP,
    ("laguna", "moe_up_dx"): UP,
    ("laguna", "moe_up_dw"): UP,
    ("laguna", "moe_down"): DOWN,
    ("laguna", "moe_down_dx"): DOWN,
    ("laguna", "moe_down_dw"): DOWN,
}


# Mellum's served kernels, one call. A prompt's expert pass: 3072 rows x
# 8 choices = 24576 pairs against the whole stack of 64: up 2*24576*2304*
# 1792, down 2*24576*896*2304, bytes the pairs on both sides and the stack
# at 2 B. A decode step's: 16 x 8 = 128 pairs and 64 * (1 - (7/8)^16) =
# 56.444 experts' weights. Flash, 32 query heads over 4 KV heads, d 128:
# the MEAN call of the 16 prompts' buckets: a window layer's query sees
# (1024*1025/2 + (s - 1024)*1024) / s keys, a full layer's (s + 1) / 2.
TOUCHED = 64 * (1 - (7 / 8) ** 16)
MELLUM_BUCKETS = ((3072, 4), (6144, 6), (9216, 3), (12288, 3))
KERNELS.update({
    ("mellum", "moe_up"): (2.0 * 24576 * 2304 * 1792,
                           2.0 * (24576 * (2304 + 1792)
                                  + 64 * 2304 * 1792)),
    ("mellum", "moe_down"): (2.0 * 24576 * 896 * 2304,
                             2.0 * (24576 * (896 + 2304)
                                    + 64 * 896 * 2304)),
    ("mellum", "moe_up_dec"): (2.0 * 128 * 2304 * 1792,
                               2.0 * (128 * (2304 + 1792)
                                      + TOUCHED * 2304 * 1792)),
    ("mellum", "moe_down_dec"): (2.0 * 128 * 896 * 2304,
                                 2.0 * (128 * (896 + 2304)
                                        + TOUCHED * 896 * 2304)),
    # the mean call of the 16 prompts' buckets (3072 x 4, 6144 x 6,
    # 9216 x 3, 12288 x 3; mean 7104 rows): operations and bytes apart
    ("mellum", "flash_fwd_win"): (
        4.0 * 32 * 128 * sum(
            n * (1024 * 1025 / 2.0 + (s - 1024) * 1024)
            for s, n in MELLUM_BUCKETS) / 16,
        (2 * 32 + 2 * 4) * 7104.0 * 128 * 2.0 + 32 * 7104.0 * 4.0),
    ("mellum", "flash_fwd_full"): (
        4.0 * 32 * 128 * sum(n * s * (s + 1) / 2.0
                             for s, n in MELLUM_BUCKETS) / 16,
        (2 * 32 + 2 * 4) * 7104.0 * 128 * 2.0 + 32 * 7104.0 * 4.0),
})


# Jamba's served kernels, one call, at the MEAN call of the 32 prompts'
# dispatches (8 in each bucket; rows x bucket = 2 x 512, 1024, 2048, 4096:
# mean 2048 positions). The scan: 5120 x (7 x 16 + 6) = 604,160 operations a
# position; bytes x, Delta, z, y float32 [positions, 5120], B and C float32
# [positions, 16], A [16, 5120], D [5120], the state [rows, 16, 5120] (rows
# 2, 1, 1, 1: mean 1.25). Flash: 20 query heads over 1 KV head, d 128,
# (s + 1) / 2 keys a query, over the dispatches that reach the kernel (the
# 512-row bucket is under FLAGS_pallas_min_seq): 1024, 2048, 4096.
JAMBA_FLASH = (1024, 2048, 4096)
KERNELS.update({
    ("jamba", "selective_scan"): (
        2048 * 604_160.0,
        4.0 * (4 * 2048 * 5120 + 2 * 2048 * 16 + 16 * 5120 + 5120
               + 1.25 * 16 * 5120)),
    ("jamba", "flash_fwd_full"): (
        sum(4.0 * 20 * 128 * s * (s + 1) / 2.0 for s in JAMBA_FLASH) / 3,
        sum((2 * 20 + 2) * s * 128 * 2.0 + 20 * s * 4.0
            for s in JAMBA_FLASH) / 3),
})


# LFM2's served kernels, one call. A decode step's grouped products: 128
# rows x 4 choices = 512 pairs and 64 * (1 - (60/64)^128) = 63.98 experts'
# weights, x [2048 -> 3072] and [1536 -> 2048]. A prefill dispatch computes
# 512 // bucket rows, at least one (2 x 256, 512, 1024, 2048 positions, 8
# prompts each: mean 1024 positions = 4096 pairs) against the whole stack.
# Flash, 32 query heads over 8 KV heads, d 64, over the dispatches that
# reach the kernel (1024 and 2048; 256 and 512 are under
# FLAGS_pallas_min_seq). The decode read without counters: the
# least any call reads, one block a slot at one byte a value.
TOUCHED_LFM2 = 64 * (1 - (60 / 64) ** 128)
LFM2_FLASH = ((1, 1024), (1, 2048))
KERNELS.update({
    ("lfm2", "moe_up_dec"): (2.0 * 512 * 2048 * 3072,
                             2.0 * (512 * (2048 + 3072)
                                    + TOUCHED_LFM2 * 2048 * 3072)),
    ("lfm2", "moe_down_dec"): (2.0 * 512 * 1536 * 2048,
                               2.0 * (512 * (1536 + 2048)
                                      + TOUCHED_LFM2 * 1536 * 2048)),
    ("lfm2", "moe_up"): (2.0 * 4096 * 2048 * 3072,
                         2.0 * (4096 * (2048 + 3072) + 64 * 2048 * 3072)),
    ("lfm2", "moe_down"): (2.0 * 4096 * 1536 * 2048,
                           2.0 * (4096 * (1536 + 2048) + 64 * 1536 * 2048)),
    ("lfm2", "flash_fwd_full"): (
        sum(r * 4.0 * 32 * 64 * s * (s + 1) / 2.0 for r, s in LFM2_FLASH) / 2,
        sum(r * ((2 * 32 + 2 * 8) * s * 64 * 2.0 + 32 * s * 4.0)
            for r, s in LFM2_FLASH) / 2),
    ("lfm2", "paged_decode_attn"): (4.0 * 32 * 256 * 64 * 128,
                                    2.0 * 8 * 256 * 64 * 128),
})


# Qwen3-Next's served kernels, one call, at the MEAN call of the 16
# prompts' dispatches (one prompt a dispatch: 3072 x 5, 4096 x 3, 6144 x 5,
# 8192 x 3; mean 5184 positions = 81 chunks of 64). The chunked rule: a
# chunk a head 2 x (2 x 64^2 x 128 + 10 x 64^3 + 3 x 64 x 128^2 + 2 x 64^2
# x 128) = 15,728,640 over 32 heads; bytes float32 q, k [5184, 2048], v, o
# [5184, 4096], g, beta [5184, 32] and the state [32, 128, 128]. A decode
# step's: 64 rows x 32 x 128 x 128 state elements, 8 operations each; read
# and written once, with q, k, v, o, g, beta. Flash, 16 query heads over 2
# KV heads, d 256. The decode read without counters: one block a slot at
# one byte a value. The experts: 5184 x 10 x 128/512 = 12960 pairs against
# the held stack of 128; a step's 160 pairs over 128 (1 - (127/128)^160).
QWEN_BUCKETS = ((3072, 5), (4096, 3), (6144, 5), (8192, 3))
TOUCHED_QWEN = 128 * (1 - (127 / 128) ** 160)
KERNELS.update({
    ("qwen3next", "gdn_prefill"): (
        81 * 32 * 15_728_640.0,
        4.0 * (5184 * (2 * 2048 + 2 * 4096 + 2 * 32) + 32 * 128 * 128)),
    ("qwen3next", "gdn_decode"): (
        64 * 8.0 * 32 * 128 * 128,
        4.0 * 64 * (2 * 32 * 128 * 128 + 2 * 2048 + 2 * 4096 + 2 * 32)),
    ("qwen3next", "flash_fwd_full"): (
        4.0 * 16 * 256 * sum(n * s * (s + 1) / 2.0
                             for s, n in QWEN_BUCKETS) / 16,
        (2 * 16 + 2 * 2) * 5184.0 * 256 * 2.0 + 16 * 5184.0 * 4.0),
    ("qwen3next", "paged_decode_attn"): (4.0 * 16 * 256 * 256 * 64,
                                         2.0 * 2 * 256 * 256 * 64),
    ("qwen3next", "moe_up"): (2.0 * 12960 * 2048 * 1024,
                              2.0 * (12960 * (2048 + 1024)
                                     + 128 * 2048 * 1024)),
    ("qwen3next", "moe_down"): (2.0 * 12960 * 512 * 2048,
                                2.0 * (12960 * (512 + 2048)
                                       + 128 * 512 * 2048)),
    ("qwen3next", "moe_up_dec"): (2.0 * 160 * 2048 * 1024,
                                  2.0 * (160 * (2048 + 1024)
                                         + TOUCHED_QWEN * 2048 * 1024)),
    ("qwen3next", "moe_down_dec"): (2.0 * 160 * 512 * 2048,
                                    2.0 * (160 * (512 + 2048)
                                           + TOUCHED_QWEN * 512 * 2048)),
})


def test_qwen3next_counts_its_decode_kernels_from_the_runs_counters():
    cfg, job = config("qwen3-next-80b-a3b-ep4-d8"), load(
        "traffic", "qwen3next_docs_8k.json")
    counts = families.load(cfg).kernel_counts
    # the decode products: 150 pairs over 88 experts a step a layer
    counters = {"engine.expert_pairs.traced": 8 * 150.0 * 117,
                "engine.experts_touched.traced": 8 * 88.0 * 117,
                "engine.sampler_dispatches.traced": 117.0}
    assert counts("moe_up_dec", cfg, job, counters=counters) == \
        pytest.approx((2.0 * 150 * 2048 * 1024,
                       2.0 * (150 * (2048 + 1024) + 88 * 2048 * 1024)),
                      rel=1e-12)
    assert counts("moe_down_dec", cfg, job, counters={}) is None
    # the paged read: 1300 live blocks a flight of bfloat16 rows
    counters = {"engine.kv_blocks_live.traced": 1300.0 * 50,
                "engine.decode_flights.traced": 50.0, "kv_item_bytes": 2}
    assert counts("paged_decode_attn", cfg, job, counters=counters) == \
        pytest.approx((4.0 * 16 * 256 * 256 * 1300,
                       2.0 * 2 * 256 * 256 * 2 * 1300), rel=1e-12)
    assert counts("paged_decode_attn", cfg, job, counters={}) is None
    # the delta rule's kernels count by shape, whatever the run read
    assert counts("gdn_decode", cfg, job, counters=counters) == \
        counts("gdn_decode", cfg, job)
    assert counts("selective_scan", cfg, job) is None
    assert counts("gdn_prefill", cfg, {"kind": "train"}) is None


def test_lfm2_counts_its_data_dependent_kernels_from_the_runs_counters():
    """The decode read at the live blocks a flight and the decode products
    at the experts a step, both over the traced interval; a run that read
    no flight there gives no count, and a share cannot pass 100 when the
    trace's calls equal the counters' flights."""
    cfg, job = config("lfm2-24b-a2b-d9"), load("traffic",
                                               "lfm2_agents_3k.json")
    counts = families.load(cfg).kernel_counts
    counters = {"engine.kv_blocks_live.traced": 300 * 512.0,
                "engine.decode_flights.traced": 300.0, "kv_item_bytes": 2,
                "engine.experts_touched.traced": 300 * 8 * 60.0,
                "engine.sampler_dispatches.traced": 300.0}
    assert counts("paged_decode_attn", cfg, job, counters=counters) == \
        (4.0 * 32 * 256 * 64 * 512, 2.0 * 8 * 256 * 64 * 2 * 512)
    assert counts("moe_up_dec", cfg, job, counters=counters)[1] == \
        2.0 * (512 * (2048 + 3072) + 60 * 2048 * 3072)
    assert counts("paged_decode_attn", cfg, job, counters={}) is None
    assert counts("moe_up_dx", cfg, job) is None    # another family's name
    assert counts("moe_up", cfg, {"kind": "train"}) is None
    # 600 calls (2 layers x 300 flights) at the memory's speed: 100%
    flights, got = 300, counts("paged_decode_attn", cfg, job,
                               counters=counters)
    floor = flops.kernel_floors({"kernel_calls.paged_decode_attn":
                                 2.0 * flights}, lambda n: got,
                                "TPU v5 lite")["kernel_floor_s."
                                               "paged_decode_attn"]
    peak = flops.peaks("TPU v5 lite")
    assert floor == pytest.approx(2 * flights * got[1]
                                  / peak["hbm_bytes_per_s"])


# Keye's served kernels are the expert layer's four: a prompt's pass of
# 4096 rows x 8 choices over the whole stack of 128 experts [2048 -> 1536]
# / [768 -> 2048]; a decode step's 8 rows x 8 choices over the experts 8
# uniform rows touch, 128 x (1 - (15/16)^8) = 51.6; and a decode row's
# selected read, the paged walk under the chosen set's mask: without a
# run's counters one block a slot at one byte a value (32 query heads on
# 4 KV heads of 128, 256-row blocks, 8 slots). The selection and a
# prompt's read are XLA ops and have no count.
TOUCHED_KEYE = 128 * (1 - (15 / 16) ** 8)
KERNELS.update({
    ("keye", "moe_up"): (2.0 * 32768 * 2048 * 1536,
                         2.0 * (32768 * (2048 + 1536) + 128 * 2048 * 1536)),
    ("keye", "moe_down"): (2.0 * 32768 * 768 * 2048,
                           2.0 * (32768 * (768 + 2048) + 128 * 768 * 2048)),
    ("keye", "moe_up_dec"): (2.0 * 64 * 2048 * 1536,
                             2.0 * (64 * (2048 + 1536)
                                    + TOUCHED_KEYE * 2048 * 1536)),
    ("keye", "moe_down_dec"): (2.0 * 64 * 768 * 2048,
                               2.0 * (64 * (768 + 2048)
                                      + TOUCHED_KEYE * 768 * 2048)),
    ("keye", "paged_decode_attn"): (4.0 * 32 * 256 * 128 * 8,
                                    2.0 * 4 * 256 * 128 * 8),
})


def test_keye_counts_its_decode_products_from_the_runs_counters():
    """The decode products' expert stack is the engine's own count over
    the traced interval, over the 6 layers."""
    cfg, job = config("keye-vl2-30b-a3b-stage0"), load(
        "traffic", "keye_longdoc_24k.json")
    counts = families.load(cfg).kernel_counts
    counters = {"engine.experts_touched.traced": 6 * 40.0 * 117,
                "engine.sampler_dispatches.traced": 117.0}
    assert counts("moe_up_dec", cfg, job, counters=counters) == pytest.approx(
        (2.0 * 64 * 2048 * 1536,
         2.0 * (64 * (2048 + 1536) + 40.0 * 2048 * 1536)), rel=1e-12)
    assert TOUCHED_KEYE == pytest.approx(51.620, abs=1e-3)
    assert KERNELS["keye", "moe_up"] == (206_158_430_208.0, 1_040_187_392.0)


def test_keye_counts_its_selected_read_from_the_runs_live_blocks():
    """The decode rows' read walks every live block under the chosen
    set's mask: its count is the live blocks a flight over the traced
    interval times one block's K and V at the pool's item size (the
    mask's bytes left out: a floor); a run that read no flight there
    gives none, and at the memory's speed the share is 100."""
    cfg, job = config("keye-vl2-30b-a3b-stage0"), load(
        "traffic", "keye_longdoc_24k.json")
    counts = families.load(cfg).kernel_counts
    counters = {"engine.kv_blocks_live.traced": 117 * 290.0,
                "engine.decode_flights.traced": 117.0, "kv_item_bytes": 2}
    got = counts("paged_decode_attn", cfg, job, counters=counters)
    assert got == (4.0 * 32 * 256 * 128 * 290, 2.0 * 4 * 256 * 128 * 2 * 290)
    assert counts("paged_decode_attn", cfg, job, counters={}) is None
    floor = flops.kernel_floors(
        {"kernel_calls.paged_decode_attn": 6.0 * 117}, lambda n: got,
        "TPU v5 lite")["kernel_floor_s.paged_decode_attn"]
    assert floor == pytest.approx(
        6 * 117 * got[1] / flops.peaks("TPU v5 lite")["hbm_bytes_per_s"])


# dots.vlm1's share: a prompt's pass of 2048 rows x 8 choices x 8 held of
# 256 = 512 rows expected over the held stack of 8 (7168 -> 2 x 2048 ->
# 7168); the decode read without a run's counters one block a slot: 32
# blocks of 256 rows, every head's score over 576 values and its weighted
# sum of 512, against the block's 576 values at 2 bytes: 242 FLOP a byte
TOUCHED_DOTS = 8 * (1 - (7 / 8) ** 8)


def _pairs_dots():
    """The multiset's 16 prompts: each one's live causal triangle, n (n +
    1) / 2 pairs a head, by hand; neither its bucket's rectangle nor the
    tiles of 1024 x 1024 the kernel runs at or under the diagonal."""
    lengths = [round(4096 * 4 ** ((i + 0.5) / 16)) for i in range(16)]
    total = 0
    for n in lengths:
        assert 4096 < n <= 16384
        total += n * (n + 1) // 2
    return total / 16.0


PAIRS_DOTS = _pairs_dots()
KERNELS.update({
    ("dotsvlm", "moe_up"): (2.0 * 512 * 7168 * 4096,
                            2.0 * (512 * (7168 + 4096) + 8 * 7168 * 4096)),
    ("dotsvlm", "moe_down"): (2.0 * 512 * 2048 * 7168,
                              2.0 * (512 * (2048 + 7168) + 8 * 2048 * 7168)),
    ("dotsvlm", "mla_decode_attn"): (2.0 * 128 * (512 + 64 + 512) * 256 * 32,
                                     576.0 * 2 * 256 * 32),
    # 32 rows x 8 x 8 / 256 = 8 pairs over 8 x (1 - (7/8)^8) experts
    ("dotsvlm", "moe_up_dec"): (2.0 * 8 * 7168 * 4096,
                                2.0 * (8 * (7168 + 4096)
                                       + TOUCHED_DOTS * 7168 * 4096)),
    ("dotsvlm", "moe_down_dec"): (2.0 * 8 * 2048 * 7168,
                                  2.0 * (8 * (2048 + 7168)
                                         + TOUCHED_DOTS * 2048 * 7168)),
    # a pass of 32 heads over the multiset's mean live pairs
    ("dotsvlm", "mla_prompt_attn"): (
        2.0 * 32 * 320 * PAIRS_DOTS,
        32 * (2.0 * PAIRS_DOTS) ** 0.5 * 576 * 2.0),
})


def test_dotsvlm_counts_its_latent_reads_from_the_runs_counters():
    """A decode step's read by hand at a toy count: 3 live blocks a
    flight: 3 x 256 keys x 128 heads x (576 + 512) x 2 FLOP against 3 x
    256 x 576 x 2 B, 241.8 FLOP a byte: at the chip's speed the floor is
    the products' (the v5e's ridge is 240.5) and a kernel that ran at it
    reads 100, never more. A prompt's read from its live pairs, not the
    bucket's rectangle; the decode products from the pairs and experts a
    step a sparse layer."""
    cfg, job = config("dots-vlm1-share32-d6"), load(
        "traffic", "dotsvlm_docs_16k.json")
    counts = families.load(cfg).kernel_counts
    counters = {"engine.kv_blocks_live.traced": 117 * 3.0,
                "engine.decode_flights.traced": 117.0, "kv_item_bytes": 2}
    got = counts("mla_decode_attn", cfg, job, counters=counters)
    assert got == (2.0 * 128 * 1088 * 768, 576.0 * 2 * 768)
    assert got == (213_909_504.0, 884_736.0)
    assert got[0] / got[1] == pytest.approx(241.78, abs=0.01)
    assert counts("mla_decode_attn", cfg, job, counters={}) is None
    peak = flops.peaks("TPU v5 lite")
    floor = flops.kernel_floors(
        {"kernel_calls.mla_decode_attn": 6.0 * 117}, lambda n: got,
        "TPU v5 lite")["kernel_floor_s.mla_decode_attn"]
    assert floor == pytest.approx(6 * 117 * got[0] / peak["bf16_flops"])
    # the share of a kernel that took exactly its products' time is 100
    assert 100.0 * floor / (6 * 117 * got[0] / peak["bf16_flops"]) \
        == pytest.approx(100.0)
    # a prompt of 4277 live rows in the 6144 bucket: its live triangle,
    # 9.1M pairs a head, where the kernel's five query tiles of 1024 run
    # 15.7M and the bucket's triangle is 18.9M
    from paddle_tpu.ops.pallas.mla_attention import prompt_pairs
    pairs = prompt_pairs(1, 6144, 4277)
    assert pairs == 4277 * 4278 // 2 == 9_148_503
    assert pairs < 1024 * 1024 * (1 + 2 + 3 + 4 + 5) < 6144 * 6144 // 2
    counters = {"engine.mla_prompt_pairs.traced": 24.0 * pairs * 3,
                "engine.mla_prompt_reads.traced": 24.0 * 3}
    f, b = counts("mla_prompt_attn", cfg, job, counters=counters)
    assert f == 2.0 * 32 * (192 + 128) * pairs      # a pass of 32 heads
    assert b == pytest.approx(32 * (2.0 * pairs) ** 0.5 * (256 + 64 + 256)
                              * 2)
    assert counts("mla_prompt_attn", cfg, job, counters={}) is None
    # the decode products: 7.5 pairs over 5.2 experts a step a layer
    counters = {"engine.expert_pairs.traced": 5 * 7.5 * 117,
                "engine.experts_touched.traced": 5 * 5.2 * 117,
                "engine.sampler_dispatches.traced": 117.0}
    assert counts("moe_up_dec", cfg, job, counters=counters) == \
        pytest.approx((2.0 * 7.5 * 7168 * 4096,
                       2.0 * (7.5 * (7168 + 4096) + 5.2 * 7168 * 4096)),
                      rel=1e-12)
    assert counts("moe_down_dec", cfg, job, counters={}) is None


@pytest.mark.parametrize("family,kernel", sorted(KERNELS))
def test_kernel_counts_are_the_hand_count(family, kernel):
    name = FAMILY_CONFIG[family]
    cfg, job = config(name), load("traffic", JOBS[family] + ".json")
    got = families.load(cfg).kernel_counts(kernel, cfg, job)
    assert got == pytest.approx(KERNELS[family, kernel], rel=1e-12)
    assert TOUCHED == pytest.approx(56.444, abs=1e-3)
    assert 2 * 16 * 8192 * 496.03125 * 128 * 2 == 33_288_093_696
    # and the floor the roofline share divides
    floors = flops.kernel_floors(
        {"kernel_calls." + kernel: 2.0},
        lambda n: families.load(cfg).kernel_counts(n, cfg, job),
        "TPU v5 lite")
    peak = flops.peaks("TPU v5 lite")
    assert floors["kernel_floor_s." + kernel] == pytest.approx(
        2.0 * max(got[0] / peak["bf16_flops"],
                  got[1] / peak["hbm_bytes_per_s"]))


@pytest.mark.parametrize("family", ["gpt2", "laguna", "mellum", "jamba",
                                    "keye", "dotsvlm"])
def test_a_kernel_the_family_has_no_count_for_is_none(family):
    name = FAMILY_CONFIG[family]
    cfg, job = config(name), load("traffic", JOBS[family] + ".json")
    counts = families.load(cfg).kernel_counts
    # (keye's decode rows read through the paged kernel, and it counts it)
    assert (counts("paged_decode_attn", cfg, job) is None) \
        == (family != "keye")
    other = {"gpt2": "flash_fwd_win", "laguna": "flash_fwd",
             "mellum": "moe_up_dx", "jamba": "flash_fwd_win",
             "keye": "flash_fwd_full", "dotsvlm": "flash_fwd_full"}[family]
    assert counts(other, cfg, job) is None        # another family's name
    # and none for a job of the other kind (serving for the families that
    # train, training for the one that serves)
    assert counts(sorted(k for f, k in KERNELS if f == family)[0], cfg,
                  {"kind": "train" if family in ("mellum", "jamba", "keye",
                                                 "dotsvlm")
                   else "open_loop"}) is None


# ---- the listing, walked: whatever BENCHMARK.json and metrics/ hold today

E2E = {m["name"] for m in BENCH["end_to_end"]}
LAYERS = {m["layer"] for m in LISTED.values()}
NOTHING = dict.fromkeys(readers.GROUPS, {})


@pytest.mark.parametrize("family", sorted(JOBS))
def test_a_familys_cell_is_its_configuration_under_its_traffic(family):
    cell = CELLS[JOBS[family]]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (FAMILY_CONFIG[family], JOBS[family], 1)
    assert reports(cell["name"], RATE[family])


@pytest.mark.parametrize("name,cell", PAIRS)
def test_an_entry_agrees_with_its_file_in_a_cell_that_can_report_it(name,
                                                                    cell):
    entry, spec = LISTED[name], SPECS[name]
    assert set(entry) == {"name", *FIELDS, "workloads"}
    assert all(spec[f] == entry[f] for f in FIELDS)
    assert cell in CELLS and reports(cell, entry["moves"])
    assert set(spec.get("what_in", {})) <= set(entry["workloads"])


@pytest.mark.parametrize("name,cell", FILE_PAIRS)
def test_a_metric_file_is_whole_and_its_kernel_is_one_its_cell_counts(
        name, cell):
    """Every file, listed or waiting for room: it is whole, a run that
    observed nothing leaves it out, and a kernel's share is over that
    kernel's time, the kernel one that the family of the cell counts for
    the cell's own job (of some cell, while the file waits)."""
    spec = SPECS[name]
    assert set(spec) >= {"reader", "what", *FIELDS} and spec["what"]
    assert {"from", "name"} <= set(spec["reader"])
    assert spec["moves"] in E2E and spec["better"] in ("lower", "higher")
    assert spec["layer"] in LAYERS
    assert readers.read(name, NOTHING) is None
    kernel, r = kernel_of(spec), spec["reader"]
    if kernel:
        floor = r["name"].startswith("kernel_floor_s.")
        assert r["over"] == ("trace.kernel_s." + kernel if floor
                             else "trace.busy_s")
        assert any(counts(kernel, c) for c in ([cell] if cell else CELLS))


#: reader name -> what its files divide it by (None: nothing)
OVER = {"engine.experts_touched": "counters.engine.sampler_dispatches",
        "engine.window_blocks_freed": "counters.engine.completed",
        "engine.prefill_tokens_live":
            "counters.engine.prefill_tokens_computed",
        "engine.state_bytes.close": None,
        "engine.sparse_keys_read": "counters.engine.sparse_keys_live",
        "engine.sparse_keys_live": "counters.engine.sampler_dispatches",
        "engine.sparse_prompt_keys_read":
            "counters.engine.sparse_prompt_keys_rect",
        "engine.index_cache_bytes.close": None}


@pytest.mark.parametrize("reader", sorted(OVER))
def test_an_engine_counter_is_read_over_the_count_it_is_a_share_of(reader):
    files = files_reading(reader)
    assert files and all(
        SPECS[n]["reader"]["from"] == "counters"
        and SPECS[n]["reader"].get("over") == OVER[reader] for n in files)


#: the part's counter -> the whole's (PR 28, PR 30, PR 32)
COUNTER_SHARES = {"engine.sampler_skipped": "engine.sampler_dispatches",
                  "engine.inputs_resident": "engine.inputs_dispatches",
                  "engine.prefill_rows_live": "engine.prefill_rows_computed"}


@pytest.mark.parametrize("name", [
    n for part in COUNTER_SHARES for n in files_reading(part)])
def test_a_counter_share_reads_both_engine_counters(name):
    part = SPECS[name]["reader"]["name"]
    whole = COUNTER_SHARES[part]
    # its fields are those of the in-place share that moves the same metric
    twins = [SPECS[n] for n in files_reading("engine.pool_inplace")
             if SPECS[n]["moves"] == SPECS[name]["moves"]]
    assert twins and all(SPECS[name][k] == twin[k]
                         for twin in twins for k in FIELDS)
    obs = {"counters": {part: 1500.0, whole: 1600.0}}
    assert readers.read(name, obs) == pytest.approx(93.75)
    # the parent's program has neither counter: nothing to read, no metric
    assert readers.read(name, {"counters": {
        "engine.pool_dispatches": 1600.0}}) is None
    # no decode dispatch in the window: no share
    assert readers.read(name, {"counters": {part: 0, whole: 0}}) is None


# ---- the cells' toy twins, rehearsed to their end

REHEARSE_ON_A_STEPPED_CLOCK = """
import sys
sys.path.insert(0, {root!r})
from perfbench import rehearse, serve


class Stepped:
    # the harness's clock, stepped: every read is 10 ms later than the
    # last, so a window of S seconds is S / 0.03 steps of the engine (the
    # closed loop reads it three times a step) whatever the CPU's speed
    now = 0.0

    def perf_counter(self):
        Stepped.now += 0.01
        return Stepped.now

    def sleep(self, seconds):
        pass


serve.time = Stepped()
sys.exit(rehearse.main({argv!r}))
"""
#: cell -> (the window, the seed, the readers its twin must have read)
TWINS = {
    "mellum_code_16k": (9, 2**31 + 77, (
        "engine.experts_touched", "engine.window_blocks_freed",
        "live_slot_share", "engine.inputs_resident", "serving.decode_step")),
    "jamba_reasoning_6k": (6, 2**31 + 33, (
        "serving.decode_step", "engine.prefill_tokens_live")),
    "lfm2_agents_3k": (6, 2**31 + 42, (
        "serving.decode_step", "engine.experts_touched",
        "engine.expert_rows_max", "engine.state_bytes.close",
        "engine.prefill_tokens_live")),
    "keye_longdoc_24k": (9, 2**31 + 46, (
        "serving.decode_step", "engine.sparse_keys_read",
        "engine.sparse_keys_live", "engine.index_cache_bytes.close",
        "engine.inputs_resident", "engine.prefill_tokens_live",
        "engine.sparse_prompt_keys_read")),
    "dotsvlm_docs_16k": (9, 2**31 + 49, (
        "serving.decode_step", "engine.latent_rows_read",
        "engine.expert_pairs", "engine.experts_touched",
        "engine.latent_cache_bytes.close", "engine.inputs_resident",
        "engine.prefill_tokens_live")),
    "qwen3next_docs_8k": (6, 2**31 + 58, (
        "serving.decode_step", "engine.experts_touched",
        "engine.expert_pairs", "engine.state_bytes.close",
        "engine.inputs_resident", "engine.prefill_tokens_live"))}


def last_line(out):
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", sorted(TWINS))
def test_a_serving_cells_toy_twin_rehearses_to_its_end(cell):
    """``rehearse.py --workload <cell> --trace 1`` in its own process exits
    0: the harness found every file by name, built the family's served
    model behind the engine's seam (contexts that cross the toy window;
    blocks for one layer and rows of state for three), ran the closed loop
    and checked 8 requests against the family's reference: correct, nothing
    leaked, and the counters' metrics were read. The harness's window is
    counted in steps and not in wall seconds: on the wall clock a 2 s
    window had to hold 8 completions of the toy twin, which a CPU shared
    by six workers did not always give (the take-up run of PR 33)."""
    seconds, seed, read = TWINS[cell]
    argv = ["--workload", cell, "--seconds", str(seconds), "--trace", "1",
            "--seed", str(seed)]
    line = last_line(subprocess.run(
        [sys.executable, "-c",
         REHEARSE_ON_A_STEPPED_CLOCK.format(root=ROOT, argv=argv)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=900))
    assert line["correct"] is True and line["failed"] == 0
    assert line["notes"]["leaked_kv_blocks"] == 0
    assert line["notes"]["checked_requests"] == 8
    assert line["notes"]["max_logit_deficit"] <= 0.05
    assert line["notes"]["completed_in_window"] >= 8
    assert all(m["value"] is None for m in line["metrics"].values())
    # the cell's own entries and no other, none of them the device trace's
    mine = {m["name"]: m for m in run.metrics_of(BENCH, "per_layer", cell)}
    assert set(line["metrics"]) <= set(mine), sorted(line["metrics"])
    assert all(mine[n]["source"] != "device_trace" for n in line["metrics"])
    assert {named(cell, r) for r in read} <= set(line["metrics"])


def test_the_training_cells_toy_twin_rehearses_to_its_end():
    """``rehearse.py --workload laguna_pretrain_8k`` exits 0: the harness
    found every file by name, built the family's train job, ran the window
    and the check against the family's reference."""
    cell = JOBS["laguna"]
    line = last_line(subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "rehearse.py"),
         "--workload", cell, "--seconds", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600))
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {
        m["name"] for m in run.metrics_of(BENCH, "end_to_end", cell)}
    assert all(m["value"] is None for m in line["metrics"].values())
    assert line["notes"]["check_loss_diff"] <= 0.05


REHEARSE_WITH_VALUES = """
import argparse, json, sys
sys.path.insert(0, {root!r})
from perfbench import rehearse, run
bench = run.load_json({root!r}, "BENCHMARK.json")
args = argparse.Namespace(workload="decode_heavy", seed=2147483659,
                          seconds=1.5, trace=1)
print(json.dumps(rehearse.run_twin(
    bench, run.find_cell(bench, "decode_heavy"), args)))
"""


@pytest.fixture(scope="module")
def decode_heavy_read():
    """``decode_heavy``'s toy twin, traced, on a real engine (its own
    process, as ``rehearse.py`` runs it, but with the values kept) -> the
    value the cell's entry over a reader got."""
    line = last_line(subprocess.run(
        [sys.executable, "-c", REHEARSE_WITH_VALUES.format(root=ROOT)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=600))
    assert line["correct"] is True and line["failed"] == 0
    return lambda reader: \
        line["metrics"][named("decode_heavy", reader)]["value"]


def test_a_rehearsal_reports_every_decode_step_as_sampler_skipped(
        decode_heavy_read):
    """The harness submits no decode parameters, so every decode dispatch
    of the window is all-greedy and the reader finds both counters."""
    assert decode_heavy_read("engine.sampler_skipped") == 100.0
    # eight rows turn over about once in thirty steps of the toy twin
    assert 80.0 <= decode_heavy_read("engine.inputs_resident") < 100.0
    assert decode_heavy_read("engine.pool_inplace") == 100.0
    assert decode_heavy_read("compiles_in_window") == 0


def test_a_rehearsal_reads_the_live_share_of_its_prefill_rows(
        decode_heavy_read):
    """The toy twin's prompts (4-16 tokens) fall in its bucket of 16, whose
    one program has ``max_slots`` = 4 rows under GPT's 512 tokens a
    dispatch; a completion frees one slot, so a dispatch carries one live
    row of four, two where two requests ended in one step."""
    assert 25.0 <= decode_heavy_read("engine.prefill_rows_live") <= 50.0
