"""Client drivers, one per kind of traffic (a traffic file's ``query``).

``bench.run.run_cell`` loads ``bench/drivers/<query>.py`` (``-`` read as
``_``) and drives the cell through it. A driver module gives:

* ``check_traffic(traffic)``: refuse a traffic file whose keys of this
  kind are missing or out of range, before any work;
* ``make_server(index, fit, cfg, traffic, cell)``: what its clients
  serve through, from the configuration's deployment (``bench.deploy``'s
  index and fit), the traffic file and the cell (its ``chips``);
* ``requests(traffic, seed, index, fit)``: the cell's requests from the
  seed, an object whose ``queries(i)`` is request ``i`` as the client
  takes it (``bench.traffic.draw`` over the populations the driver
  offers);
* ``Client(server, spans, wide_tier=True)``: one closed-loop client with
  ``serve(q) -> out`` and ``record(q, out, latency)``, the window's
  ``counters`` (``queries`` among them), ``latencies`` and ``answers``,
  and ``state``, the device arrays to free before the reference runs;
  ``wide_tier=False`` switches the program's wide re-serve off (the
  control that ``correct`` must fail);
* ``warm(client, reqs, spans) -> str``: run every program and shape the
  window will drive, returning a note for the log;
* ``check(client, reqs, index, fit) -> (checks, wrong_rows)``: every
  answer of the window against the brute-force reference
  (``bench.reference``), ``checks`` being ``{name: (value, limit)}``.
"""
