"""Work counts of the DeepSeek-V2-Lite decode tick (``bench/work_mla.py``)
against sums by hand at the published widths."""
import json
from pathlib import Path

import pytest

from bench import work_mla

ROOT = Path(__file__).resolve().parents[2]
CFG = json.loads((ROOT / "bench" / "configs" / "deepseek-v2-lite.json")
                 .read_text())


def test_parameter_counts():
    # q 6.29M + kv_a 1.18M + kv_b 2.10M + o 4.19M per layer
    assert work_mla._mla_params(CFG) == 13_762_560
    assert work_mla.expert_params(CFG) == 8_650_752
    # 27 MLA + the dense MLP + 26 x (shared + router) + the head
    assert work_mla.token_params(CFG) == (27 * 13_762_560 + 67_239_936
                                         + 26 * (17_301_504 + 131_072)
                                         + 209_715_200)
    assert work_mla.latent_row_bytes(CFG) == 1152


def test_one_full_tick_moves_about_5_6_gb():
    """16 tokens, ~6.3 of 8 held experts reached per MoE layer, 16
    contexts of ~1,000 rows in 27 layers: ~2.2 GB of weights outside the
    experts, ~2.8 GB of experts, ~0.5 GB of latent rows."""
    counts = {"decode_ticks": 1, "moe_experts_read": 164,
              "decode_rows_attended": 16 * 1000 * 27,
              "moe_pairs_total": 16 * 6 * 26, "moe_pairs_local": 16 * 26}
    flops, nbytes = work_mla.decode_work(CFG, counts)
    assert nbytes == pytest.approx(5.6e9, rel=0.05)
    weights = work_mla.token_params(CFG) * 2
    assert weights == pytest.approx(2.2e9, rel=0.01)
    assert flops == pytest.approx(
        2 * work_mla.token_params(CFG) * 16
        + 2 * work_mla.expert_params(CFG) * 16 * 26
        + 2 * 16 * (576 + 512) * 16 * 1000 * 27)
