"""Published-config fidelity: every assigned architecture matches the
numbers in the assignment table, and analytic parameter counts land in the
advertised size class."""
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config

# (arch, layers, d_model, heads, kv, d_ff, vocab)
TABLE = {
    "phi3-medium-14b": (40, 5120, 40, 10, 17920, 100352),
    "deepseek-coder-33b": (62, 7168, 56, 8, 19200, 32256),
    "h2o-danube-1.8b": (24, 2560, 32, 8, 6912, 32000),
    "qwen1.5-0.5b": (24, 1024, 16, 16, 2816, 151936),
    "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
    "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
    "mamba2-2.7b": (64, 2560, 0, 0, 0, 50280),
    "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
    "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
    "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
    "granite-4.0-h-small": (40, 4096, 32, 8, 0, 100352),
}

# advertised total parameter counts (approximate class), ±25%
SIZES = {
    "phi3-medium-14b": 14e9,
    "deepseek-coder-33b": 33e9,
    "h2o-danube-1.8b": 1.8e9,
    "qwen1.5-0.5b": 0.5e9,
    "jamba-v0.1-52b": 52e9,
    "whisper-tiny": 39e6,
    "mamba2-2.7b": 2.7e9,
    "qwen3-moe-30b-a3b": 30e9,
    "qwen3-moe-235b-a22b": 235e9,
    "qwen2-vl-7b": 7e9,
    "granite-4.0-h-small": 32e9,
}

ACTIVE = {"qwen3-moe-30b-a3b": 3e9, "qwen3-moe-235b-a22b": 22e9,
          "jamba-v0.1-52b": 12e9, "granite-4.0-h-small": 9e9}


def test_registry_covers_all_ten():
    assert sorted(ARCH_IDS) == sorted(TABLE)


@pytest.mark.parametrize("arch", sorted(TABLE))
def test_published_numbers(arch):
    cfg = get_config(arch)
    L, d, H, KV, ff, V = TABLE[arch]
    assert cfg.num_layers == L
    assert cfg.d_model == d
    assert cfg.num_heads == H
    assert cfg.num_kv_heads == KV
    if cfg.family == "moe":
        assert cfg.moe_d_ff == ff
    elif ff:
        assert cfg.d_ff == ff
    assert cfg.vocab_size == V


@pytest.mark.parametrize("arch", sorted(SIZES))
def test_param_count_in_size_class(arch):
    cfg = get_config(arch)
    n = cfg.param_count()
    lo, hi = 0.7 * SIZES[arch], 1.35 * SIZES[arch]
    assert lo < n < hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9:.1f}, {hi/1e9:.1f}]B"


@pytest.mark.parametrize("arch", sorted(ACTIVE))
def test_active_param_count(arch):
    cfg = get_config(arch)
    n = cfg.param_count(active_only=True)
    tgt = ACTIVE[arch]
    assert 0.6 * tgt < n < 1.6 * tgt, f"{arch}: active {n/1e9:.2f}B vs {tgt/1e9:.1f}B"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_vocab_padding_is_tp_divisible(arch):
    cfg = get_config(arch)
    assert cfg.padded_vocab % 16 == 0          # model axis of the prod mesh
    assert cfg.padded_vocab % 128 == 0         # MXU lane alignment
    assert 0 <= cfg.padded_vocab - cfg.vocab_size < cfg.vocab_pad_multiple


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_config_is_small(arch):
    cfg = get_smoke_config(arch)
    assert cfg.param_count() < 5e6
    # one whole period of the layer pattern, or 8 layers at the most
    assert cfg.num_layers <= max(8, len(cfg.layer_pattern))
    # family preserved
    assert cfg.family == get_config(arch).family
    assert cfg.layer_pattern == get_config(arch).layer_pattern


def test_pattern_consistency():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        assert cfg.num_layers % len(cfg.layer_pattern) == 0
        if cfg.family == "ssm":
            assert cfg.attention_free
        if cfg.moe_num_experts:
            assert cfg.moe_top_k > 0
