"""``chainermn_tpu.dataflow`` — the async hot-loop layer.

ChainerMN's core lesson is that scaling dies on the host: the accelerator
step is fast and everything serialized around it — data feeding, loss
fetches, snapshot writes — becomes the wall. The
jitted steps already donate buffers; this package takes the HOST loop
around them off the critical path, in three pieces:

- :class:`DevicePrefetcher` — batches drawn, collated, and
  ``device_put`` onto the mesh by a producer thread, ``depth`` ahead:
  H2D transfer overlaps device compute instead of following it.
- :class:`LossWindow` + :func:`device_fetch` — dispatch-ahead stepping:
  losses stay on device and are fetched batched every ``window`` steps
  (one round trip closes the whole window), bounding in-flight dispatch;
  ``device_fetch`` is the completion barrier that returns the values.
- ``MultiNodeCheckpointer.save_async`` (``extensions.checkpoint``) —
  ``device_get`` on the training thread (the consistency point), then
  serialize + CRC footer + atomic rename + GC on a writer thread.

Wired end to end by :func:`chainermn_tpu.training.fit` and
``resilience.resilient_fit(async_save=True)``: a pipelined step costs
about max(step, loader) of wall time instead of step + loader.
"""

from chainermn_tpu.dataflow.dispatch import LossWindow, device_fetch
from chainermn_tpu.dataflow.prefetch import DevicePrefetcher

__all__ = ["DevicePrefetcher", "LossWindow", "device_fetch"]
