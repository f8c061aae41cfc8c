"""Management layer tests: CLI discovery, monitor, checkpointing, web client."""

import threading
import time

import numpy as np
import pytest


def test_cli_experiment_list(capsys):
    from p2pfl_tpu.cli import main

    assert main(["experiment", "list"]) == 0
    out = capsys.readouterr().out
    assert "mnist" in out and "spmd_mnist" in out
    # piped/non-TTY stdout (pytest capture) keeps the plain parseable
    # two-column form — no box glyphs, no ANSI
    assert "┌" not in out and "\033[" not in out


def test_cli_experiment_list_fancy_on_tty(capsys, monkeypatch):
    """Reference-parity UX (Typer/Rich stand-in, reference cli.py:30-125):
    banner + box-drawing table on an interactive UTF-8 terminal."""
    import p2pfl_tpu.cli as cli

    monkeypatch.setattr(cli, "_fancy", lambda: True)
    assert cli.main(["experiment", "list"]) == 0
    out = capsys.readouterr().out
    assert "┌" in out and "│ experiment" in out and "└" in out
    assert "mnist" in out


def test_cli_table_renders_rows():
    from p2pfl_tpu.cli import _table

    t = _table(["a", "bb"], [["x", "y"], ["longer", "z"]])
    lines = t.splitlines()
    assert lines[0].startswith("┌") and lines[-1].startswith("└")
    assert len({len(line) for line in lines}) == 1  # aligned columns
    assert "longer" in t and "bb" in t


def test_cli_unknown_experiment():
    from p2pfl_tpu.cli import main

    assert main(["experiment", "run", "nope"]) == 1


def test_node_monitor_reports():
    from p2pfl_tpu.management.node_monitor import NodeMonitor
    from p2pfl_tpu.settings import Settings

    Settings.RESOURCE_MONITOR_PERIOD = 0.05
    seen = []
    mon = NodeMonitor("test-node", report_fn=lambda n, m, v: seen.append((m, v)))
    mon.start()
    time.sleep(0.4)
    mon.stop()
    metrics = {m for m, _ in seen}
    assert "cpu_percent" in metrics and "ram_percent" in metrics


def test_web_services_swallow_failures():
    """A dead dashboard must never raise into the caller."""
    from p2pfl_tpu.management.web_services import WebServices

    ws = WebServices("http://127.0.0.1:1", "key", timeout=0.2)
    ws.register_node("n1")  # nothing listening — must not raise
    ws.send_global_metric("e", 0, "acc", "n1", 0.5)


def test_web_services_posts(tmp_path):
    """Round-trip against a local HTTP server: headers + payloads correct."""
    import http.server
    import json

    received = []

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            body = self.rfile.read(int(self.headers["Content-Length"]))
            received.append((self.path, self.headers.get("x-api-key"), json.loads(body)))
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.end_headers()
            self.wfile.write(b'{"node_key": "k1"}')

        def log_message(self, *a):  # silence
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        from p2pfl_tpu.management.web_services import WebServices

        ws = WebServices(f"http://127.0.0.1:{srv.server_port}", "secret")
        ws.register_node("n1", is_simulated=True)
        ws.send_local_metric("exp", 1, "loss", "n1", 5, 0.25)
        assert received[0][0] == "/node" and received[0][1] == "secret"
        assert received[1][2]["metric"] == "loss" and received[1][2]["step"] == 5
        assert ws._node_key == "k1"
    finally:
        srv.shutdown()


def test_learner_checkpoint_roundtrip(tmp_path):
    from p2pfl_tpu.learning.checkpoint import restore_learner, save_learner
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.learning.learner import JaxLearner
    from p2pfl_tpu.models import mlp

    data = FederatedDataset.synthetic_mnist(n_train=256, n_test=64)
    learner = JaxLearner(mlp(), data, batch_size=64)
    learner.fit()
    import jax

    want = jax.tree.leaves(learner.params)

    other = JaxLearner(mlp(seed=9), data, batch_size=64)
    save_learner(str(tmp_path / "ckpt"), learner, round=3)
    restore_learner(str(tmp_path / "ckpt"), other)
    got = jax.tree.leaves(other.params)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def _mlp_federation(seed):
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.models import mlp
    from p2pfl_tpu.parallel import SpmdFederation

    data = FederatedDataset.synthetic_mnist(n_train=1024, n_test=128)
    return SpmdFederation.from_dataset(mlp(seed=seed), data, n_nodes=4, batch_size=64, vote=False)


def _lora_federation_without_kept_state(seed):
    from p2pfl_tpu.learning.dataset import FederatedDataset
    from p2pfl_tpu.models.transformer import TransformerConfig, tiny_transformer
    from p2pfl_tpu.parallel import SpmdLoraFederation

    cfg = TransformerConfig(vocab_size=64, dim=32, n_layers=1, n_heads=2, n_kv_heads=2, ffn_hidden=64, lora_rank=2)
    data = FederatedDataset.synthetic_lm(vocab_size=64, seq_len=16, n_train=32, n_test=16)
    return SpmdLoraFederation.from_dataset(
        tiny_transformer(seq_len=16, seed=seed, cfg=cfg), data, n_nodes=4, batch_size=4, vote=False,
        keep_opt_state=False,
    )


@pytest.mark.parametrize("build", [_mlp_federation, _lora_federation_without_kept_state])
def test_federation_checkpoint_roundtrip(tmp_path, build):
    """Params, round and optimizer state round-trip; a federation that keeps
    no optimizer state (``opt_state is None``) saves none and restores None."""
    import jax

    fed = build(0)
    fed.run_round()
    fed.save(str(tmp_path / "fed"))

    fed2 = build(5)
    fed2.restore(str(tmp_path / "fed"))
    assert fed2.round == 1
    assert (fed2.opt_state is None) == (fed.opt_state is None) == (build is _lora_federation_without_kept_state)
    for a, b in zip(jax.tree.leaves((fed.params, fed.opt_state)), jax.tree.leaves((fed2.params, fed2.opt_state))):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    fed2.run_round()  # and the restored federation runs on
