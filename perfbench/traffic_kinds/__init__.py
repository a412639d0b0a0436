"""Traffic kinds: one general generator and driver per kind of traffic.

A traffic mix is a data file ``perfbench/traffic/<name>.json`` whose
``kind`` names a module here. A kind module has ``ROLE`` (which of a
configuration's builders it drives: ``train`` or ``serve``) and the
functions ``plan``, ``warm_up``, ``drive``, ``end_to_end`` and ``check``
(see ``perfbench/README.md``).
"""
