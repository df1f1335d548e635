"""Which device ops belong to which layer, by their HLO text in the trace.

The rules were checked by hand against the chip traces of PR 22
(``python -m sortbench.reduce <file.xplane.pb>``).  The program does not
name its jitted programs or its bitonic kernels yet (``kernel_metadata=
{}``, every module is ``jit_f``), so the rules read the op's kind:

* the exchange: XLA's collectives (the lax engine, the splitter and
  count exchanges), the remote-DMA Pallas kernel (the only Pallas op
  whose metadata names a mesh axis) and the fused pack
  (``ops/exchange.py``, ``%fused_pass_pack``);
* the local sort: every other Pallas op, the bitonic stages
  (``ops/bitonic.py``): six on one chip at 2^27 keys, and on four chips
  the sort of each chip's keys before the exchange and of what it
  received after.
"""

#: a Pallas kernel on the TPU
PALLAS = 'custom_call_target="tpu_custom_call"'
#: the remote-DMA kernel's metadata names the mesh axis it sends along
REMOTE_DMA = '"mesh_axes"'
PACK = "%fused_pass_pack"
#: XLA's collectives, synchronous or started asynchronously
COLLECTIVES = (" all-to-all(", " all-to-all-start(", " collective-permute(",
               " collective-permute-start(", " all-gather(",
               " all-gather-start(", " all-reduce(", " all-reduce-start(")


def is_exchange(hlo: str) -> bool:
    return (hlo.startswith(PACK) or (PALLAS in hlo and REMOTE_DMA in hlo)
            or any(c in hlo for c in COLLECTIVES))


def is_local_sort(hlo: str) -> bool:
    return PALLAS in hlo and not is_exchange(hlo)
