"""The daemon with ONE weakened path switched on: the control that a run
with `--control <mode>` puts in the program's place.  The comparison has
to call such a run not correct, through the very path a sound run takes.

    python3 control_daemon.py <mode> -c <yaml> --sim ...

Both modes break the deployment's `durable_ack` guarantee in the way a
later PR that wants a cheaper submit would be tempted to:

* `fsync_off`: the program's own weakened path, `WriteAheadLog(path,
  fsync=False)`: records are written, never fsynced.
* `late_write`: the acknowledgement runs one write ahead of the log: what
  a `write` hands over reaches the file only with the next one (an ack
  ahead of its group's write; a process that dies loses the newest group).

Nothing of the benchmark's own runs imports this file."""

import runpy
import sys


class OneWriteBehind:
    """A file whose `write` holds the text back until the next `write`
    (or `close`); everything else is the file's own."""

    def __init__(self, fh):
        self._fh, self._held = fh, ""

    def write(self, text):
        out, self._held = self._held, text
        self._fh.write(out)
        return len(text)

    def close(self):
        self._fh.write(self._held)
        self._held = ""
        self._fh.close()

    def __getattr__(self, name):
        return getattr(self._fh, name)


def switch_on(mode: str) -> None:
    from cranesched_tpu.ctld import wal

    if mode == "fsync_off":
        init = wal.WriteAheadLog.__init__

        def __init__(self, path, fsync=True):
            init(self, path, fsync=False)
        wal.WriteAheadLog.__init__ = __init__
    elif mode == "late_write":
        # every file the log opens (at start, after a rotation) is wrapped
        wal.WriteAheadLog._fh = property(
            lambda self: self.__dict__["_fh_behind"],
            lambda self, fh: self.__dict__.__setitem__(
                "_fh_behind", OneWriteBehind(fh)))
    else:
        raise SystemExit(f"control_daemon.py: no mode {mode!r} "
                         "(fsync_off, late_write)")


if __name__ == "__main__":
    # the yardstick's modules (lib/) must not shadow anything the daemon
    # imports: Python put this file's directory first
    sys.path.pop(0)
    switch_on(sys.argv.pop(1))
    sys.argv[0] = "cranesched_tpu.ctld_main"
    runpy.run_module("cranesched_tpu.ctld_main", run_name="__main__")
