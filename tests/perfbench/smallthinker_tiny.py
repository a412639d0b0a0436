"""The window-and-full TRAINING cell's stand-in for the rehearsal checkout,
as data: a tiny configuration with the published keys that keeps the shape
of the thing (one period ``F W W W``: a full layer without rotary, then
three layers with a window of 8 positions and rotary, so a sequence of 32
crosses the window several times; grouped queries 4 over 2; a router over 8
experts that reads the block's input and chooses 3; ReLU-gated experts of
which 4 are held, from the third on), the accepted tiny mix of the
``train_repeat`` kind, and the cell that joins them. ``tests/conftest.py``
registers them in ``rehearsal.py``'s tables, so that no file that was there
is edited; ``test_perfbench_smallthinker.py`` and
``tests/unit/test_smallthinker.py`` import the same names."""
import copy

import rehearsal

CELL = "smallthinker-21b-train-16k"
CONFIG = "smallthinker-21b-a3b-ep4-8layer"

TINY_SMALLTHINKER = {
    "name": "tiny-smallthinker", "source": "test",
    "builders": {"train": "smallthinker_train"},
    "head_dim": 8, "hidden_size": 32, "max_position_embeddings": 64,
    "model_name": "tiny", "moe_ffn_hidden_size": 16,
    "moe_num_active_primary_experts": 3, "moe_num_primary_experts": 8,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1, 0, 1, 1, 1], "rope_scaling": None,
    "rope_theta": 10000,
    "sliding_window_layout": [0, 1, 1, 1, 0, 1, 1, 1],
    "sliding_window_size": 8, "tie_word_embeddings": False,
    "vocab_size": 128,
    "model": {"family": "smallthinker", "vocab_size": 128},
    "moe": {"routed_over": 8, "experts_held": [2, 4],
            "expert_activation": "relu", "router_input": "block"},
    "train": {"param_dtype": "float32", "compute_dtype": "float32",
              "remat": True, "remat_policy": "full",
              "use_flash_attention": False, "fused_head_ce": 16,
              "ds_config": copy.deepcopy(
                  rehearsal.CONFIGS["tiny-gpt"]["train"]["ds_config"])},
    "reference": {"module": "smallthinker", "batch": [1, 48],
                  "limits": {"loss": 1e-4, "agreement": 0.99,
                             "grad_experts": 1e-3,
                             "grad_attention_window": 1e-3,
                             "grad_attention_full": 1e-3,
                             "grad_router": 1e-3}},
    "reduced": []}
TINY_CELL = {"name": "tiny-smallthinker-train", "config": "tiny-smallthinker",
             "traffic": "tiny-train", "chips": 1, "why": "rehearsal"}
STAND_IN = {CELL: TINY_CELL["name"]}
# the case of the contract test that holds every configuration to
# ``reduced == []``, expected to fail for one that lists its cut;
# ``test_reduced_is_exactly_what_differs_from_the_catalog`` of
# ``test_perfbench_smallthinker.py`` replaces it
PREDATES_REDUCED = f"test_configuration_entry_and_file[{CONFIG}]"


def register(rehearsal):
    rehearsal.CONFIGS.setdefault(TINY_SMALLTHINKER["name"], TINY_SMALLTHINKER)
    if TINY_CELL not in rehearsal.CELLS:
        rehearsal.CELLS.append(TINY_CELL)
    rehearsal.STAND_IN.update(STAND_IN)
