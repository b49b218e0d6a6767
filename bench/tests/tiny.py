"""A cell at a size the CPU tests can hold: the named configuration with its
widths cut to the launcher's ``--smoke`` model (2 layers, d_model 64, 4 heads
of 16, vocab 512, float32) and 4 sequences of 32 tokens per replica."""
import copy

from bench import cells

# gaps at this size in float32 are round-off (under 1e-5); the bf16 control
# reads 4e-5 and up on loss_gap, 1.5e-3 and up on the others
LIMITS = {"loss_gap": 1e-5, "grad_gap": 2e-4, "delta_gap": 2e-4}


def tiny_cell(config_name: str = "qwen3-0.6b", dp: int = 1,
              norm_eps: float | None = None) -> cells.Cell:
    """``norm_eps``, where given, replaces the configuration's in the
    reference."""
    bench = cells.load_benchmark()
    config = copy.deepcopy(cells.load_json(
        cells.BENCH / "configs" / f"{config_name}.json"))
    if norm_eps is not None:
        config["reference"]["norm_eps"] = norm_eps
    heads = 4
    config["config"].update(
        hidden_size=64, intermediate_size=128, num_hidden_layers=2,
        num_attention_heads=heads,
        num_key_value_heads=min(config["config"]["num_key_value_heads"],
                                heads),
        head_dim=16, vocab_size=512, torch_dtype="float32")
    config["launcher"] = ["--smoke", "--d-model", "64",
                          "--smoke-mesh", f"1,{dp},1"]
    traffic = copy.deepcopy(cells.load_json(
        cells.BENCH / "traffic" / "gossip-s1024-b4.json"))
    traffic.update(seq_len=32, seqs_per_chip=4)
    traffic["tokens"]["pool_tokens"] = 8192
    return cells.Cell(name="tiny", config_name=config_name,
                      traffic_name="tiny", chips=dp, config=config,
                      traffic=traffic, limits=dict(LIMITS),
                      end_to_end=bench["end_to_end"],
                      per_layer=bench["per_layer"])
