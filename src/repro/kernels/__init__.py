"""Pallas TPU kernels for the perf-critical compute layers.

Layer map (bottom-up):

- sorted_probe      — SPF equal-range probe into one sorted key column
                      (VPU broadcast-compare; emits both rank sides)
- run_probe         — fused membership + rank of targets within per-row
                      sorted runs (window-masked compare-reduce; the
                      bind-join membership test of Def. 5)
- delta_probe, owned_probe, fingerprint, replay
                    — the delta overlay's ranks, the sharded store's
                      ownership-masked probe, wave digests, cache replay
- layout            — the 2-D int32 block layouts every probe kernel uses
                      (Mosaic lowers no 64-bit vectors)
- flash_attention   — fused attention for the LM architectures
- ref               — pure-jnp oracles (kernel ground truth AND the
                      non-TPU execution path)
- ops               — THE dispatch layer: every engine/benchmark call-site
                      routes through ops.* (TPU: Pallas; elsewhere: ref;
                      ``ops.FORCE`` overrides).  Nothing above this package
                      picks a backend.
"""
