"""The program's names on the device timeline (``p2pfl.*`` scopes, kernel
names, host annotations) and the benchmark's reduction by scope.

``op_name`` metadata is written by JAX, not by a backend, so the compiled text
of the CPU is evidence for the TPU too: no topology fixture, no libtpu here.
"""

import re
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmark import scope_reduce, trace_reduce
from p2pfl_tpu.learning.dataset import FederatedDataset
from p2pfl_tpu.management.profiling import DEVICE_SCOPES, get_dispatch_counts, reset_dispatch_counts, scope
from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer
from p2pfl_tpu.parallel import SpmdFederation, SpmdLoraFederation
from p2pfl_tpu.settings import Settings

FIXTURES = Path(scope_reduce.__file__).resolve().parent / "fixtures"
ROUND_SCOPES = ("grad", "optimizer", "fold")
SSM_SCOPES = ("ssm_conv", "ssm_scan_fwd", "ssm_scan_bwd")  # a Mamba layer's; read by readers/scope_named.py
MOE_SCOPES = ("mla", "moe_route", "moe_experts", "moe_gmm", "moe_combine")  # latent attention's and an expert layer's; same reader
CONV_SCOPES = ("short_conv", "qk_norm")  # the gated short convolution's, and the per-head q / k norms'; same reader
LM_SCOPES = ("head",)  # every CausalLM's: the head and the loss
WINDOW_SCOPES = ("flash_win_fwd", "flash_win_bwd", "attn_gate", "post_norm")  # a sliding layer's kernels, the output gate, the sandwich's second norms; same reader
KDA_SCOPES = ("kda_conv", "kda_gate", "kda_fwd", "kda_bwd")  # a delta-rule layer's convolutions, gates and the op's two directions; same reader
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _mlp_federation():
    from p2pfl_tpu.models import mlp

    data = FederatedDataset.synthetic_mnist(n_train=256, n_test=64)
    return SpmdFederation.from_dataset(mlp(), data, n_nodes=4, batch_size=16, vote=False)


def _lora_federation():
    cfg = TransformerConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_hidden=128,
        lora_rank=4, lora_mlp=True, remat=True, scan_layers=True, remat_policy="mlp_qkv",
    )
    model = tiny_transformer(seq_len=128, cfg=cfg, attn="flash")  # interpret mode off-TPU
    data = FederatedDataset.synthetic_lm(vocab_size=256, seq_len=128, n_train=64, n_test=16)
    return SpmdLoraFederation.from_dataset(model, data, n_nodes=4, batch_size=2, vote=False, node_chunk=2)


def _hybrid_federation():
    cfg = TransformerConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=1, ffn_hidden=128, rope_theta=None,
        layer_pattern=("mamba", "attention"), ssm_dt_rank=8, lora_rank=4, lora_mlp=True, remat=True,
        scan_layers=True, remat_policy="ssm",
    )
    model = tiny_transformer(seq_len=128, cfg=cfg, attn="flash")
    data = FederatedDataset.synthetic_lm(vocab_size=256, seq_len=128, n_train=64, n_test=16)
    return SpmdLoraFederation.from_dataset(model, data, n_nodes=4, batch_size=2, vote=False, node_chunk=2)


def _expert_federation():
    cfg = TransformerConfig(
        vocab_size=256, dim=64, n_layers=3, n_heads=4, n_kv_heads=4, ffn_hidden=160, rope_theta=1e6,
        layer_pattern=("mla_dense", "mla_experts", "mla_experts"), lora_rank=4, lora_mlp=True, remat=True,
        scan_layers=True, norm_eps=1e-5, q_lora_rank=24, kv_lora_rank=16, qk_nope_dim=12, qk_rope_dim=4,
        v_head_dim=16, routed_experts=8, experts_per_token=2, expert_hidden=32, shared_experts=1,
        routed_scale=1.8, expert_tile_m=8,
    )
    model = tiny_transformer(seq_len=128, cfg=cfg, attn="flash")
    data = FederatedDataset.synthetic_lm(vocab_size=256, seq_len=128, n_train=64, n_test=16)
    return SpmdLoraFederation.from_dataset(model, data, n_nodes=4, batch_size=2, vote=False, node_chunk=2)


def _conv_expert_federation():
    cfg = TransformerConfig(
        vocab_size=256, dim=64, n_layers=6, n_heads=4, n_kv_heads=2, ffn_hidden=160, rope_theta=1e6,
        leading_pattern=("conv_dense", "conv_dense"), layer_pattern=("attention_experts", "conv_experts"), qk_norm=True,
        lora_rank=4, lora_mlp=True, remat=True, scan_layers=True, norm_eps=1e-5, routed_experts=8,
        experts_per_token=2, expert_hidden=32, expert_tile_m=8,
    )
    model = tiny_transformer(seq_len=128, cfg=cfg, attn="flash")
    data = FederatedDataset.synthetic_lm(vocab_size=256, seq_len=128, n_train=64, n_test=16)
    return SpmdLoraFederation.from_dataset(model, data, n_nodes=4, batch_size=2, vote=False, node_chunk=2)


def _window_expert_federation():
    cfg = TransformerConfig(
        vocab_size=256, dim=64, n_layers=6, n_heads=4, n_kv_heads=2, head_dim=32, ffn_hidden=160, rope_theta=1e4,
        leading_pattern=("swa_dense", "swa_dense"), layer_pattern=("swa_experts", "full_experts"), qk_norm=True,
        attn_window=32, attn_gate=True, post_norms=True, embed_scale=8.0, tie_head=False,
        lora_rank=4, lora_mlp=True, remat=True, scan_layers=True, norm_eps=1e-5, routed_experts=8, experts_held=4,
        first_expert=4, experts_per_token=2, expert_hidden=32, shared_experts=1, expert_tile_m=8,
    )
    model = tiny_transformer(seq_len=128, cfg=cfg, attn="flash")
    data = FederatedDataset.synthetic_lm(vocab_size=256, seq_len=128, n_train=64, n_test=16)
    return SpmdLoraFederation.from_dataset(model, data, n_nodes=4, batch_size=2, vote=False, node_chunk=2)


def _linear_expert_federation():
    cfg = TransformerConfig(
        vocab_size=256, dim=64, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16, ffn_hidden=160, rope_theta=None,
        layer_pattern=("full_experts", "kda_experts", "kda_experts", "kda_experts"), attn_gate=True, tie_head=False,
        lora_rank=4, lora_mlp=True, remat=True, scan_layers=True, norm_eps=1e-5, routed_experts=8, experts_held=4,
        experts_per_token=2, expert_hidden=32, shared_experts=1, expert_tile_m=8, conv_taps=4, kda_chunk=32,
    )
    model = tiny_transformer(seq_len=128, cfg=cfg, attn="flash")
    data = FederatedDataset.synthetic_lm(vocab_size=256, seq_len=128, n_train=64, n_test=16)
    return SpmdLoraFederation.from_dataset(model, data, n_nodes=4, batch_size=2, vote=False, node_chunk=2)


def _lower_spmd_round(fed):
    from p2pfl_tpu.parallel.spmd import spmd_round

    perm, mask, sel_idx = fed._round_inputs(1)
    return spmd_round.lower(
        fed.params, fed.opt_state, fed.x_all, fed.y_all, perm, mask, fed._samples, sel_idx,
        module=fed.module, tx=fed.tx, agg=fed.aggregator, trim=fed.trim, clip_tau=fed.clip_tau,
        out_sharding=fed._shard, keep_opt_state=fed.keep_opt_state,
        dp_keys=fed._dp_round_keys(), **fed._algo_kwargs(0),
    )


@pytest.fixture(scope="module")
def compiled_op_names():
    """``{engine: the op_names of its compiled round}``, compiled once."""
    lowered = {
        "spmd": _lower_spmd_round(_mlp_federation()), "spmd_lora": _lora_federation().lower_round(epochs=1),
        "spmd_lora_hybrid": _hybrid_federation().lower_round(epochs=1),
        "spmd_lora_moe": _expert_federation().lower_round(epochs=1),
        "spmd_lora_conv_moe": _conv_expert_federation().lower_round(epochs=1),
        "spmd_lora_window_moe": _window_expert_federation().lower_round(epochs=1),
        "spmd_lora_linear_moe": _linear_expert_federation().lower_round(epochs=1),
    }
    return {engine: set(OP_NAME.findall(low.compile().as_text())) for engine, low in lowered.items()}


@pytest.mark.parametrize(
    "engine,scope",
    [("spmd", s) for s in ROUND_SCOPES]
    + [("spmd_lora", s) for s in DEVICE_SCOPES if s not in SSM_SCOPES + MOE_SCOPES + CONV_SCOPES + WINDOW_SCOPES + KDA_SCOPES]
    + [("spmd_lora_hybrid", s) for s in DEVICE_SCOPES if s not in MOE_SCOPES + CONV_SCOPES + WINDOW_SCOPES + KDA_SCOPES]
    + [("spmd_lora_moe", s) for s in DEVICE_SCOPES if s not in SSM_SCOPES + CONV_SCOPES + WINDOW_SCOPES + KDA_SCOPES]
    + [("spmd_lora_conv_moe", s) for s in DEVICE_SCOPES if s not in SSM_SCOPES + ("mla",) + WINDOW_SCOPES + KDA_SCOPES]
    # sliding AND full layers in one period: the windowed kernels' scopes beside the plain ones'
    + [("spmd_lora_window_moe", s) for s in DEVICE_SCOPES if s not in SSM_SCOPES + ("mla", "short_conv") + KDA_SCOPES]
    # delta-rule and gated GQA layers in one period, every one an expert layer
    + [("spmd_lora_linear_moe", s) for s in DEVICE_SCOPES if s not in SSM_SCOPES + CONV_SCOPES + ("mla", "flash_win_fwd", "flash_win_bwd", "post_norm")],
)
def test_scope_is_in_the_compiled_round(compiled_op_names, engine, scope):
    assert any(f"p2pfl.{scope}" in name for name in compiled_op_names[engine])


@pytest.mark.parametrize("engine", ["spmd", "spmd_lora"])
def test_forward_reforward_backward_fall_out_of_the_grad_scope(compiled_op_names, engine):
    """The re-forward is the model's (``TransformerConfig.remat``): the step
    itself checkpoints nothing, so the MLP round has no ``remat`` bucket."""
    buckets = {scope_reduce.classify(name) for name in compiled_op_names[engine]}
    assert set(scope_reduce.PARTITION) - buckets == ({"remat"} if engine == "spmd" else set())


def test_scope_names_are_spelled_in_one_place():
    import p2pfl_tpu

    named = (ROUND_SCOPES, scope_reduce.SUB_SHARES, SSM_SCOPES, MOE_SCOPES, CONV_SCOPES, LM_SCOPES, WINDOW_SCOPES, KDA_SCOPES)
    assert {s for group in named for s in group} == set(DEVICE_SCOPES)
    sources = Path(p2pfl_tpu.__file__).parent.rglob("*.py")
    assert [p.name for p in sources if '"p2pfl."' in p.read_text()] == ["profiling.py"]
    with pytest.raises(ValueError, match="gard"):
        scope("gard")


@pytest.mark.parametrize("bwd_mode,names", [
    ("fused", {"p2pfl_flash_fwd", "p2pfl_flash_bwd_fused"}),
    ("split", {"p2pfl_flash_fwd", "p2pfl_flash_bwd_dq", "p2pfl_flash_bwd_dkv"}),
])
def test_flash_kernels_carry_their_names(bwd_mode, names):
    from p2pfl_tpu.ops.flash_attention import FlashConfig, flash_attention

    cfg = FlashConfig(block_q=64, block_k=64, bwd_mode=bwd_mode)
    q = jnp.zeros((1, 128, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, cfg, True).astype(jnp.float32).sum()

    assert "p2pfl_flash_fwd" in str(jax.make_jaxpr(loss)(q, q, q))
    grad = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    assert set(re.findall(r"p2pfl_flash_\w+", grad)) == names


PATHS = [
    # the five the TPU compiler kept for a toy round (v5e:2x2, PR 24's planning)
    ("jit(round_)/while/body/p2pfl.grad/jvp()/while/body/closed_call/p2pfl.base_matmul/dot_general", "fwd"),
    ("jit(round_)/while/body/p2pfl.grad/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/"
     "p2pfl.base_matmul/dot_general", "remat"),
    ("jit(round_)/while/body/p2pfl.grad/transpose(jvp())/while/body/closed_call/checkpoint/p2pfl.base_matmul/dot_general", "bwd"),
    ("jit(round_)/while/body/p2pfl.optimizer/mul", "opt"),
    ("jit(round_)/p2pfl.fold/reduce_sum", "fold"),
    # flax writes the module's name into jvp()
    ("jit(spmd_lora_round)/shard_map/vmap()/while/body/closed_call/p2pfl.grad/transpose(jvp(CausalLM))/while/body/"
     "closed_call/checkpoint/layers/block/attn/p2pfl.flash_bwd/p2pfl_flash_bwd_fused", "bwd"),
    ("jit(spmd_lora_round)/p2pfl.grad/jvp(CausalLM)/while/body/closed_call/layers/block/attn/p2pfl.flash_fwd/pallas_call", "fwd"),
    ("jit(spmd_lora_round)/shard_map/vmap()/while/body/closed_call/while/body/dynamic_update_slice", "unscoped"),
    ("jit(spmd_lora_round)/while/body/closed_call/checkpoint/rematted_computation/layers/block/mlp_norm/reduce_sum", "unscoped"),
    ("", "unscoped"),
]


@pytest.mark.parametrize("path,bucket", PATHS)
def test_classify(path, bucket):
    assert scope_reduce.classify(path) == bucket


def test_sub_shares_are_read_wherever_they_sit():
    path = PATHS[1][0]
    assert scope_reduce.scopes_of(path) == ["grad", "base_matmul"]
    assert scope_reduce.tail(path).startswith("p2pfl.grad/transpose(jvp())")
    assert scope_reduce.tail("jit(f)/a/b/c/d/e") == "b/c/d/e"


def _copy(name: str, tmp_path: Path) -> Path:
    return Path(shutil.copyfile(FIXTURES / name, tmp_path / name))


@pytest.mark.parametrize("name", ["small_trace.xplane.pb", "scoped_trace.xplane.pb"])
def test_reduction_on_a_recorded_tpu_trace(name, tmp_path):
    pytest.importorskip("xprof")
    before = sorted(p.name for p in FIXTURES.iterdir())
    path = _copy(name, tmp_path)
    names = scope_reduce.op_names(str(path))
    trace = trace_reduce.load_xplane(str(path))
    got = scope_reduce.by_bucket(trace, names, scope_reduce.module_runs(str(path)))
    all_ops = sum(op[4] for dev in trace["devices"].values() for op in dev["ops"])
    assert sum(got["buckets"].values()) == got["total_ns"] <= all_ops and got["missing"] == 0
    if name == "small_trace.xplane.pb":
        assert names[("14598692844800812001", "f.1")] == "jit(f)/pallas_call"
        assert not got["scoped"] and got["buckets"]["unscoped"] == got["total_ns"] == all_ops
    else:
        assert got["scoped"] and all(ns > 0 for ns in (*got["buckets"].values(), *got["shares"].values()))
        flash = got["shares"]["flash_fwd"] + got["shares"]["flash_bwd"]
        assert flash == round(trace_reduce.mosaic_seconds(trace)[0] * 1e9)
    assert sorted(p.name for p in FIXTURES.iterdir()) == before  # xprof's cache file went to tmp_path


class _Job:
    name = "cell"

    def __init__(self):
        self.lines = []

    def say(self, line):
        self.lines.append(line)


def _context(name: str, tmp_path: Path, monkeypatch) -> dict:
    from benchmark.readers import scope as reader

    path = tmp_path / "cell" / "plugins" / "profile" / "t"
    path.mkdir(parents=True)
    shutil.copyfile(FIXTURES / name, path / "host.xplane.pb")
    monkeypatch.setattr(reader, "OUT", tmp_path)
    return {
        "job": _Job(), "trace": trace_reduce.load_xplane(str(path / "host.xplane.pb")),
        "shapes": {"steps_per_program_run": 4}, "intervals": [0.002, 0.002],
    }


def test_reader_reports_per_step_round_and_share(tmp_path, monkeypatch):
    pytest.importorskip("xprof")
    from benchmark.readers import scope as reader

    context = _context("scoped_trace.xplane.pb", tmp_path, monkeypatch)
    got = {b: reader.read(context, bucket=b, per="step") for b in ("fwd", "remat", "bwd", "opt")}
    fold = reader.read(context, bucket="fold", per="round")
    unscoped = reader.read(context, bucket="unscoped", per="share")
    total = context["scope"]["total_ns"] / 1e6 / context["scope"]["executions"]  # ms an execution, of 4 steps
    assert sum(got.values()) * 4 + fold + unscoped / 100.0 * total == pytest.approx(total, rel=1e-9)
    table = [line for line in context["job"].lines if line.startswith("scope: bucket")]
    assert len(table) == len(scope_reduce.PARTITION)  # printed once, not once a metric
    assert any("identity flash" in line and "ratio 1.00000" in line for line in context["job"].lines)
    assert any(line.startswith("scope: top") and "p2pfl.grad" in line for line in context["job"].lines)
    with pytest.raises(ValueError):
        reader.read(context, bucket="fwd", per="fortnight")


def test_reader_leaves_the_metrics_out_for_a_program_without_scopes(tmp_path, monkeypatch):
    pytest.importorskip("xprof")
    from benchmark.readers import scope as reader

    context = _context("small_trace.xplane.pb", tmp_path, monkeypatch)
    assert reader.read(context, bucket="unscoped", per="share") is None
    assert reader.read(context, bucket="fwd", per="step") is None
    assert sum("NO p2pfl.* scope" in line for line in context["job"].lines) == 1


def _host_spans(tmp_path: Path, fed, rounds: int) -> list[str]:
    float(fed.run_round(epochs=1)["train_loss"])  # compile outside the trace
    reset_dispatch_counts()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(rounds):
            float(fed.run_round(epochs=1)["train_loss"])
    finally:
        jax.profiler.stop_trace()
    files = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    return [name for name, _, _ in trace_reduce.load_xplane(str(files[-1]))["host"]]


@pytest.mark.parametrize("engine,site", [("spmd", "spmd_round"), ("spmd_lora", "spmd_lora_round")])
def test_round_phases_are_on_the_host_timeline_and_only_the_dispatch_counts(engine, site, tmp_path, monkeypatch):
    monkeypatch.setattr(Settings, "TELEMETRY_JAX_ANNOTATIONS", True)
    fed = _mlp_federation() if engine == "spmd" else _lora_federation()
    spans = _host_spans(tmp_path, fed, rounds=2)
    for name in ("p2pfl:round_perm", "p2pfl:round_put", f"p2pfl:{site}"):
        assert spans.count(name) == 2, (name, spans)
    assert get_dispatch_counts() == {site: 2}
