"""Example: ARCHES-switched serving through the resident campaign service.

Two halves of the paper-7 generalization ("only the experts and telemetry
inputs change"):

1. **The serving expert bank** — two decode-attention experts (exact
   full-cache vs windowed) behind the same Pallas switch kernel that
   routes channel-estimation experts, emitting per-decode-step KPMs
   (expert KL, agreement, cache occupancy) a policy would switch on.
2. **The serving control plane** — in production the switch does not run
   as a one-shot script loop: campaigns are submitted to the resident
   ``repro.service`` and driven over its northbound HTTP API.  The demo
   starts the service in-process, submits a switched campaign as
   ``CampaignSpec`` JSON over ``POST /campaigns``, polls segment progress
   and spec_hash provenance from ``GET /campaigns/<id>``, reads live
   per-segment telemetry from ``GET /telemetry``, and drains gracefully.

    PYTHONPATH=src python examples/serve_switched.py
"""

import json
import tempfile
import time
import urllib.request

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compilation_cache
from repro.core.session import CampaignSpec, PolicySpec, SwitchSpec, spec_hash
from repro.models.config import get_config
from repro.models.model import Model
from repro.serving.switched import SwitchedDecoder, SwitchedDecodeConfig
from repro.service import CampaignService, JsonlExporter
from repro.service.api import ServiceAPI


def _get(url: str):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read().decode())


def _post(url: str, payload: str = "null"):
    req = urllib.request.Request(
        url, data=payload.encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read().decode())


def expert_bank_demo() -> None:
    """The serving expert pair and its per-step switch telemetry."""
    cfg = get_config("granite-20b", reduced=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dec = SwitchedDecoder(model, SwitchedDecodeConfig(window=8))

    batch = 2
    cache = model.init_cache(batch, 128)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (batch, 16), 0,
                                cfg.vocab)
    _, cache = model.prefill(params, prompt, cache)
    print(f"== serving expert bank: {cfg.name}, batch={batch}, "
          f"experts = exact vs window-8 attention ==")

    tokens = jnp.ones((batch, 1), jnp.int32)
    names = {0: "exact ", 1: "window"}
    # per-sequence mode vector, the serving analogue of the per-UE mode
    # vector; decisions would come from the policy bank the service runs
    for step, mode in enumerate(([0, 1], [1, 1], [0, 0], [1, 0])):
        logits, cache, kpms = dec.step(jnp.asarray(mode), params, tokens,
                                       cache)
        tokens = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        row = "/".join(names[m] for m in mode)
        print(f"step {step} experts={row} kl={kpms['expert_kl']:.4f} "
              f"agree={kpms['expert_agree']*100:3.0f}% "
              f"cache={kpms['cache_occupancy']*100:3.0f}%")
    print("(same SlotSwitch register + Pallas switch kernel as the PHY "
          "case; KPMs feed the policy bank)\n")


def service_demo() -> None:
    """Submit -> poll -> telemetry -> drain over the northbound API."""
    n_phase = 8
    spec = CampaignSpec(
        path="closed_loop",
        scenario="good_poor_good",
        scenario_args=(("poor_start", n_phase), ("poor_end", 2 * n_phase)),
        n_ues=4,
        n_slots=3 * n_phase,
        seed=42,
        policies=(PolicySpec(kind="threshold", feature="snr",
                             threshold=18.0, hysteresis=2.0),),
        switch=SwitchSpec(window_slots=2),
    )

    with tempfile.TemporaryDirectory() as state:
        jsonl = f"{state}/telemetry.jsonl"
        svc = CampaignService(
            state, max_segment_slots=n_phase,
            exporters=[JsonlExporter(jsonl)],
        ).start()
        api = ServiceAPI(svc).start()
        print(f"== resident campaign service on {api.url} "
              f"(state dir: checkpoints + status, telemetry -> JSONL) ==")

        cid = _post(api.url + "/campaigns", spec.to_json())["campaign_id"]
        print(f"POST /campaigns -> campaign_id {cid} "
              f"[spec {spec_hash(spec)}]")

        last = None
        while True:
            st = _get(api.url + f"/campaigns/{cid}")
            key = (st["state"], st["segments_done"])
            if key != last:
                print(f"GET  /campaigns/{cid[:5]}..: {st['state']:9s} "
                      f"segment {st['segments_done']}/{st['n_segments']} "
                      f"checkpoints {st['checkpoint_steps']}")
                last = key
            if st["state"] in ("completed", "failed", "cancelled"):
                break
            time.sleep(0.05)
        if st["state"] != "completed":
            raise SystemExit(f"campaign ended {st['state']}: {st['error']}")
        assert st["spec_hash"] == spec_hash(spec)  # provenance carried

        print("\nGET  /telemetry — per-segment samples off the ring:")
        for s in _get(api.url + "/telemetry?n=8"):
            print(f"  seg {s['seg_idx']} slots [{s['t0']},{s['t1']}): "
                  f"AI share {s['ai_share']:4.0%}  "
                  f"throughput {s['throughput_bps'] / 1e6:5.1f} Mbps  "
                  f"flops {s['executed_flops'] / 1e9:.2f} G")

        health = _get(api.url + "/health")
        print(f"\nGET  /health: {health['status']}, "
              f"workers={health['workers']}, "
              f"campaigns={health['campaign_states']}, "
              f"telemetry exported={health['telemetry']['exported']} "
              f"dropped={health['telemetry']['dropped']}")

        _post(api.url + "/drain")
        api.stop()
        if not svc.drain(timeout=60):
            raise SystemExit("drain timed out")
        with open(jsonl) as f:
            rows = sum(1 for _ in f)
        print(f"POST /drain -> graceful exit; {rows} telemetry rows "
              "exported losslessly")
    print("(kill the service instead of draining and a restart resumes "
          "the campaign bitwise — see tests/test_service.py)")


def main():
    enable_compilation_cache()
    expert_bank_demo()
    service_demo()


if __name__ == "__main__":
    main()
