"""Serving demo: continuous batching as the order-preserving farm.

Requests with different prompt lengths arrive while earlier ones are still
decoding; the admitter (Emitter) recycles batch slots through the SPMC page
pool, a cache position per slot isolates requests, and the collector emits
results in submission order.  Under the hood ``ServeEngine.run`` is now a
skeleton expression — ``Source(requests) ∘ Farm(decode_step,
feedback=still_generating)`` — lowered to the thread graph; the decode tick
circulates the wrap-around SPSC ring until loop quiescence.

Run:  PYTHONPATH=src python examples/serve_demo.py
"""
import numpy as np

from repro.configs import ARCHS
from repro.launch.serve import Request, ServeEngine

cfg = ARCHS["phi3-mini-3.8b"].smoke()
eng = ServeEngine(cfg, max_batch=3, max_len=256, seed=0)

rng = np.random.default_rng(0)
for i in range(9):
    plen = int(rng.integers(2, 9))
    eng.submit(Request(rid=i, prompt=list(rng.integers(0, cfg.vocab_size, plen)),
                       max_new=6))

results = eng.run()
print(f"served {len(results)} requests in {eng.steps_run} engine steps "
      f"(slots recycled {eng.pool.allocated}x through {eng.max_batch} pages)")
for r in results:
    print(f"  tag={r.tag} rid={r.rid} prompt_len={len(r.prompt)} out={r.generated}")
assert [r.tag for r in results] == sorted(r.tag for r in results)
print("serve_demo OK")
