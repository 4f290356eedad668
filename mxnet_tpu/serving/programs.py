"""What a model gives ``ServingEngine``: its programs and, layer by
layer, the KIND of cache each layer keeps.

The engine is the same loop for every model (scheduler, admission,
allocator, block tables, sampling, spans); what differs is what one
decode step and one prefill compute, and what they keep between steps.
A servable net answers ``net.serving_programs()`` with one
:class:`ServingPrograms`, and the engine reads everything model-shaped
from it.  Nothing in the engine names a model.

Cache kinds:

- :class:`KVPages` — paged K and V pools ``[num_pages, page_size,
  K_kv * D]`` a layer (fp32 / bf16 / int8 pages, grouped-query heads,
  prefix sharing, speculative decoding: everything SERVING.md section 2
  describes).  A model that names no kinds keeps these in every layer;
- :class:`LatentPages` — one paged pool ``[num_pages, page_size,
  width]`` a layer for each of ``widths``, all addressed by the same
  allocator and block table.  ``(width,)``: a row serving as key and
  value (latent attention).  ``(width, index_width)``: beside the latent
  rows a narrower row a token that a query scores to CHOOSE which latent
  rows it reads (learned sparse attention);
- :class:`SlotState` — arrays with one row a SLOT (``[num_slots + 1,
  *shape]``, the last row scratch), freed with the slot, never paged.
  Its ``role`` says what the rows are: "state", a recurrent state (and
  a short convolution's tail) that a prefill overwrites at admission, a
  chunk run hands on to the next, and a decode step updates in place;
  or "ring", the newest ``window`` K and V rows of a sliding-window
  attention layer, position ``p`` at row ``p % window``, which a decode
  step overwrites a row at a time and a prefill reads before it leaves
  its own last rows.

A model may mix kinds layer by layer: the pools are made for the paged
layers only, a slot's bytes count its :class:`SlotState` arrays
(``state_bytes_per_slot``) and a token's its pages
(``kv_bytes_per_token``).

Prefix reuse, speculative decoding, int8 pages and a reduced KV-head
count are defined for a model of :class:`KVPages` alone; the engine
refuses them for a model with any other kind, in a sentence that names
the kind.  A CHUNKED prefill may meet every kind: the chunk before left
what this one reads in the slot's pages, its ring's rows, or its state
rows.  A chunk run at ``prefix_len`` 0 starts a recurrent state from
zero, whatever the slot's rows hold; a later one starts from the rows
and writes its own final state back (``gigachat35.py``; the engine
counts these runs as ``serving.state.handoffs``).  ``ling3.py`` still
prefills a prompt whole.
"""
from __future__ import annotations

import collections

KVPages = collections.namedtuple("KVPages", "heads head_dim")
LatentPages = collections.namedtuple("LatentPages", "widths")
#: ``arrays``: ``((name, per-slot shape, dtype or None for the pools'
#: dtype), ...)``; ``role``: "state" or "ring"
SlotState = collections.namedtuple("SlotState", "arrays role",
                                   defaults=("state",))


class ServingPrograms:
    """The model's side of the engine's contract.

    - ``n_heads``: query heads (the programs' static ``n_heads``);
    - ``max_len``: the longest sequence the model admits;
    - ``decode_params(net, kv_heads=None)``: the parameter tree the
      programs take (leaf ``wte`` is ``[vocab, units]``);
    - ``decode_step`` / ``prefill`` (/ ``spec_decode_step``): the
      contract of ``gpt.paged_decode_step`` / ``gpt.paged_prefill`` /
      ``gpt.paged_spec_decode_step``.  A model with :class:`SlotState`
      layers takes the slot as ``prefill(..., slot=)``, a chunk run
      too;
    - ``cache_kinds``: one kind a layer, or None for :class:`KVPages`
      everywhere at the engine's ``kv_heads``;
    - ``decode_stats``: names of the float32 counts a decode step
      returns in its trailing ``aux["stats"]`` (empty: the programs
      return no ``aux``);
    - ``chunked_prefill``: the prefill program admits a prompt a CHUNK
      at a time: its ``prefix_len`` argument is the position of the
      chunk's first row, ``prompt_len`` the prompt's length so far, and
      it reads what the chunks before wrote from the slot's own pages
      (and rings, and recurrent states).
      The engine then admits prompts longer than ``max_prefill_len``
      and runs the program as often as a prompt needs;
    - ``config_key``: whatever the programs bake in that the input
      shapes do not show (it joins the engine's compile-cache key).
    """

    def __init__(self, n_heads, max_len, decode_params, decode_step,
                 prefill, spec_decode_step=None, cache_kinds=None,
                 decode_stats=(), config_key="", chunked_prefill=False):
        self.n_heads = int(n_heads)
        self.max_len = int(max_len)
        self.decode_params = decode_params
        self.decode_step = decode_step
        self.prefill = prefill
        self.spec_decode_step = spec_decode_step
        self.cache_kinds = None if cache_kinds is None \
            else tuple(cache_kinds)
        self.decode_stats = tuple(decode_stats)
        self.config_key = str(config_key)
        self.chunked_prefill = bool(chunked_prefill)

    @property
    def has_aux(self):
        return bool(self.decode_stats)
