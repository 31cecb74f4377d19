"""The feed's exactly-once check, driven through the harness on the CPU at a
tiny size."""
import bench_tiny

import repro.data.pipeline as pipeline_mod


def test_token_altered_at_the_source_is_not_correct(monkeypatch):
    orig = pipeline_mod.SyntheticCorpus.effect

    def altered(self, desc, from_offset=0):
        out = orig(self, desc, from_offset)
        for shard in out:
            if shard["shard"] == 3:
                shard["tokens"][7] = (shard["tokens"][7] + 1) % self.vocab
        return out
    monkeypatch.setattr(pipeline_mod.SyntheticCorpus, "effect", altered)
    r = bench_tiny.run("tiny-mamba.steady")
    assert not r["correct"]
    assert r["checks"]["feed_mismatches"]["value"] == 1


def test_mamba_cell_traced_on_cpu_reads_only_host_metrics():
    r = bench_tiny.run("tiny-mamba.steady", trace=True)
    assert r["correct"], r["checks"]
    # no device plane on the CPU: the device readers return nothing
    for name in ("device_idle_share", "mixer_device_ms", "ffn_device_ms",
                 "optimizer_device_ms"):
        assert name not in r["metrics"]
    assert "ckpt_save_s" in r["metrics"] and "feed_wait_ms" in r["metrics"]
