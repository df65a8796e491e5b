"""The engines the broadcast loop drives, one module each.

``benchmark.harness.sim_args`` lists this package and picks the one module
that accepts a configuration's parsed ``run_sim`` arguments, so a new
engine arrives as a new file here. The harness keeps what every engine
shares: the origins and keys of each broadcast, its ``reset``/``dispatch``/
``fetch`` spans and the timers of ``reset_ms`` and ``loop_ms_per_round``.
A module defines:

- ``HONOURED``: the ``run_sim`` options it honours. An option set away
  from its default and not named here names a path the engine does not
  drive, and the module does not accept the arguments.
- ``accepts(args)``: whether it drives the path the arguments select.
- ``Engine(args, rumors, builder=None)``, the program's calls for one cell
  (``builder``, where given, stands in for the program's overlay build):
  ``build()`` (the overlay and its plan, on the device when it returns),
  ``reset(origins, key)`` (a fresh state), ``run(state)`` (the round loop
  to the target), ``readout(state)`` (device scalars of the final coverage
  and round, and the rumor slots' infection rounds of the n peers in peer
  order), ``overlay()`` (the peer-indexed CSR on the host), ``law()`` (the
  reference's degree law of that overlay) and ``release()``.
- optionally ``compare``, the comparison that decides ``correct``, with
  ``benchmark.check.compare``'s arguments and result; that one is used
  where the module has none.
"""
