"""Kernels: ``bench/kernels/<kernel>.py`` serves a configuration whose
``kernel`` is ``<kernel>``. Each file defines

- ``program(config, elem_bytes)``: the request template's ``KviProgram``,
  with the configuration's fixed weights and zero data; it imports the
  program under test inside the function only;
- ``expected(config, inputs)``: the plain numpy reference's outputs by
  name, each ``(R, ...)``, for a batch of requests' inputs by name; it
  imports nothing of the program;
- ``work(config)``: ``(operations, bytes)`` of one request, counted from
  the configuration's shapes (see ``bench/work.py``).
"""
