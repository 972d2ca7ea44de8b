"""Loop drivers, one module per loop kind; a traffic file names its loop
(``"loop"``) and the harness calls ``loops.<loop>.run``."""
