"""One small reader per kind of per-layer metric: ``read(context, **args)``
returns the number, or ``None`` when there is nothing to read (the harness then
leaves the metric out of the line). ``context`` holds the reduced trace
(``trace``), the window (``window``, ``intervals``), the driver's shapes
(``shapes``), the set-up compile split (``setup_split``), the published peaks
(``peak``), the ``device`` entry and the ``job``. A ``layer_metrics/<m>.json``
names its reader and the arguments it is called with."""
