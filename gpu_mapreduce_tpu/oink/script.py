"""The OINK input-script interpreter.

Reference: ``oink/input.{h,cpp}`` — line reader with ``&`` continuation,
quote-aware ``#`` comments and ``$``/``${}`` variable substitution
(``input.cpp:258-379``), built-ins clear/echo/if/include/jump/label/log/
next/print/shell/variable (``input.cpp:497-796``), the OINK commands
input/mr/output/set, CommandStyle registry dispatch with ``-i``/``-o``
switch parsing (``input.cpp:417-468``), and named-MR method dispatch
(``input.cpp:473-484``).  Plus the ``oink/oink.cpp`` command-line
switches ``-in/-log/-screen/-echo/-var``.

Single-process redesign notes: the reference reads lines on rank 0 and
MPI_Bcasts them (``input.cpp:130-148``) — here the interpreter is host
Python driving device-parallel MapReduce objects, so no line broadcast
exists; command timing keeps the reference's semantics (elapsed seconds
of the last command, exposed as the ``time`` EQUAL keyword) without the
barriers.  ``-partition`` multi-world runs split the device mesh into
per-world sub-meshes driven by concurrent interpreter threads — see
``universe.py``.
"""

from __future__ import annotations

import os
import shutil
import sys
import time as _time
import weakref
from typing import List, Optional, TextIO

from ..core.runtime import MRError
from .command import COMMANDS
from .mrscript import MRScriptDispatch, expand_path_variable
from .objects import ObjectManager
from .variables import Variables


class OinkScript:
    """One interpreter instance: variable table + object manager + log.

    ``comm``: optional mesh (forwarded to every MR the script creates).
    ``screen``: None → stdout, False → silent, or a file-like.
    ``obj``: a caller-owned :class:`ObjectManager` — the serve/ daemon
    hands each session its own namespace (pre-loaded with tenant budget
    defaults), so two concurrent sessions both running ``mr x`` never
    collide; when given, its ``comm`` wins."""

    def __init__(self, comm=None, screen=None, logfile: Optional[str] = None,
                 world=None, obj: Optional[ObjectManager] = None):
        self.obj = obj if obj is not None else ObjectManager(comm=comm)
        self.variables = Variables(world=world)
        self.dispatch = MRScriptDispatch(self.obj, self.variables)
        self.screen: Optional[TextIO]
        if screen is None:
            self.screen = sys.stdout
        elif screen is False:
            self.screen = None
        else:
            self.screen = screen
        self.logfile: Optional[TextIO] = open(logfile, "w") if logfile \
            else None
        self.echo_screen = False       # reference default: echo log only
        self.echo_log = True
        self.deltatime = 0.0           # `time` keyword (input.cpp:463)
        # through a weak reference: a closure over ``self`` here makes a
        # cycle, and a dropped script's datasets (device memory) would wait
        # for the cyclic collector (PERF.md §6, PR 27)
        me = weakref.ref(self)
        self.variables.specials["time"] = lambda: me().deltatime
        self.variables.specials["nprocs"] = lambda: me()._nprocs()
        # label scanning + file stack (reference label_active/infiles)
        self._label_active = False
        self._labelstr = ""
        self._jump_skip = False
        self._jump_to: Optional[tuple] = None   # (filename-or-SELF, lines)
        # ft/ journaling + resume state (doc/reliability.md): a journal
        # armed by MRTPU_JOURNAL records every completed command and
        # auto-checkpoints the named MRs; resume replays the recorded
        # lines, skipping the first _ft_skip command EXECUTIONS
        # (builtins re-run so loop variables and jumps reproduce), then
        # restores the MRs from _ft_restore and continues live
        from ..ft.journal import from_env as _ft_from_env
        self._ft_journal = _ft_from_env(script_mode=True)
        self._ft_skip = 0
        self._ft_restore: Optional[tuple] = None   # (ckpt record, dir)
        self._ft_resuming = False
        # resume_into sets this when the restored checkpoint was taken
        # on a DIFFERENT mesh width than this interpreter runs — the
        # serve/ daemon surfaces it as meta.resharded (degraded mode)
        self._ft_resharded = False
        self._ft_depth = 0
        self._ft_pending_begin: Optional[tuple] = None
        # post-command hooks: callables invoked with the script after
        # EVERY completed non-builtin command (after its journal record
        # + auto-checkpoint).  The serve/ mesh autoscaler's live
        # promotion rides here; a raising hook is dropped, never fatal.
        self.post_cmd: List = []

    def _nprocs(self) -> int:
        # query the backend directly — creating (and leaking until the
        # next command cleanup) a temp MR per `$p` substitution
        # accumulated live objects
        if not hasattr(self, "_nprocs_cache"):
            comm = self.obj.comm
            if comm is None or isinstance(comm, int):
                self._nprocs_cache = 1
            else:
                from ..parallel.mesh import mesh_axis_size
                self._nprocs_cache = mesh_axis_size(comm)
        return self._nprocs_cache

    def close(self):
        if self.logfile:
            self.logfile.close()
            self.logfile = None

    # ------------------------------------------------------------------
    # output plumbing
    # ------------------------------------------------------------------
    def _emit(self, text: str):
        if self.screen is not None:
            self.screen.write(text)
        if self.logfile is not None:
            self.logfile.write(text)

    def _echo(self, line: str):
        if self._label_active:
            return
        if self.echo_screen and self.screen is not None:
            self.screen.write(line + "\n")
        if self.echo_log and self.logfile is not None:
            self.logfile.write(line + "\n")

    # ------------------------------------------------------------------
    # driving (reference Input::file / Input::one)
    # ------------------------------------------------------------------
    def run_file(self, filename: str):
        with open(filename) as f:
            lines = f.read().splitlines()
        self._run_script(lines, filename)

    def run_string(self, text: str):
        self._run_script(text.splitlines(), "<string>")

    def _run_script(self, lines: List[str], name: str):
        """Top-level driver: with a journal armed, the outermost run
        stages its lines as the pending ``begin`` record — written
        LAZILY at the first completed command, so a script that only
        runs builtins (e.g. the one-line `resume <dir>` runbook entry
        with MRTPU_JOURNAL still pointing at the same directory) never
        writes a bogus begin that would shadow the real script's on the
        next resume.  Nested runs (``include``) don't re-begin."""
        j = self._ft_journal
        if j is not None and self._ft_depth == 0 and not self._ft_resuming:
            self._ft_pending_begin = (list(lines), name)
        self._ft_depth += 1
        try:
            if self._ft_depth == 1:
                # request-scoped trace context (obs/context.py): a
                # top-level script run is ONE request — its spans,
                # journal records and quarantine records all carry one
                # trace_id.  ensure_scope reuses an enclosing context
                # (a serve/ session wrapping this script stays one
                # request) and no-ops under MRTPU_PROFILE=0; nested
                # include/jump runs arrive at depth > 1 and never
                # re-scope
                from ..obs import get_tracer, names
                from ..obs.context import ensure_scope
                # ... and one root span (cat entry): the run's CPU and
                # off-CPU seconds, context switches and what JAX built
                # under it; an include opens none
                with ensure_scope(label=f"oink:{name}"), \
                        get_tracer().span(names.OINK_SCRIPT,
                                          cat=names.ENTRY, script=name):
                    self._run_lines(lines, name)
            else:
                self._run_lines(lines, name)
        finally:
            self._ft_depth -= 1

    def _run_lines(self, lines: List[str], filename: str):
        i = 0
        while i < len(lines):
            # '&' continuation (input.cpp:117-126)
            line = lines[i]
            while line.rstrip().endswith("&") and i + 1 < len(lines):
                line = line.rstrip()[:-1] + lines[i + 1]
                i += 1
            i += 1
            self.one(line)
            if self._jump_to is not None:
                target, tlines = self._jump_to
                self._jump_to = None
                if target == "SELF":
                    i = 0          # rewind (input.cpp:672)
                else:
                    self._run_lines(tlines, target)
                    return
        if self._label_active:
            raise MRError("Label wasn't found in input script")

    def one(self, line: str) -> Optional[str]:
        """Parse + execute a single command line; returns the command
        word (reference Input::one)."""
        self._echo(line)
        stripped = _strip_comment(line)
        if not self._label_active:
            stripped = self._substitute(stripped)
        words = _split_args(stripped)
        if not words:
            return None
        command, args = words[0], words[1:]
        if self._label_active and command != "label":
            return None
        self._execute(command, args)
        return command

    # ------------------------------------------------------------------
    # substitution (reference Input::substitute) — quote-aware $x / ${x}
    # ------------------------------------------------------------------
    def _substitute(self, s: str) -> str:
        out = []
        quote = ""
        i = 0
        while i < len(s):
            c = s[i]
            if c == "$" and not quote:
                if i + 1 < len(s) and s[i + 1] == "{":
                    j = s.find("}", i + 2)
                    if j < 0:
                        raise MRError("Invalid variable name")
                    name = s[i + 2:j]
                    i = j + 1
                else:
                    if i + 1 >= len(s):
                        raise MRError("Invalid variable name")
                    name = s[i + 1]
                    i += 2
                value = self.variables.retrieve(name)
                if value is None:
                    raise MRError(f"Substitution for illegal variable "
                                  f"{name!r}")
                out.append(value)
                continue
            if quote and c == quote:
                quote = ""
            elif not quote and c in "\"'":
                quote = c
            out.append(c)
            i += 1
        return "".join(out)

    # ------------------------------------------------------------------
    # dispatch (reference Input::execute_command)
    # ------------------------------------------------------------------
    _BUILTINS = ("clear", "echo", "if", "include", "jump", "label", "log",
                 "next", "print", "shell", "variable",
                 "input", "mr", "output", "set", "resume")

    def _execute(self, command: str, args: List[str]):
        if command in self._BUILTINS:
            # resume replay: builtins re-run so loop variables and
            # control flow reproduce — EXCEPT `shell`, whose arbitrary
            # filesystem side effects (mv/rm) already happened before
            # the checkpoint and must not replay
            if self._ft_skip > 0 and command == "shell":
                return
            getattr(self, "cmd_" + command)(args)
            return
        if self._ft_skip > 0:
            # resume replay: the first _ft_skip command EXECUTIONS are
            # already durable in the restore checkpoint — skip them,
            # then load the checkpointed MRs.  ANY non-builtin word
            # counts: a skipped registered command may be what names
            # the MR a later prefix line dispatches on (`-o NULL x`
            # then `x ...`), so `x` not being in obj.named yet is
            # expected, not an unknown command
            self._ft_skip -= 1
            if self._ft_skip == 0:
                self._ft_apply_restore()
            return
        # the pending begin lands BEFORE the first command starts: a
        # crash mid-command-1 must still leave a resumable journal,
        # while a builtins-only script (the `resume <dir>` one-liner)
        # never writes one
        self._ft_flush_begin()
        if command in COMMANDS:
            self._run_registered(command, args)
            self._ft_cmd_done(command)
            return
        if command in self.obj.named:
            from ..obs import get_tracer
            t0 = _time.perf_counter()
            with get_tracer().span(f"oink.{command}", cat="oink",
                                   args=" ".join(args)):
                self.dispatch.run(command, args)
            self.deltatime = _time.perf_counter() - t0
            self._ft_cmd_done(command)
            return
        raise MRError(f"Unknown command: {command}")

    def _ft_flush_begin(self):
        j = self._ft_journal
        if j is not None and self._ft_pending_begin is not None:
            lines, name = self._ft_pending_begin
            self._ft_pending_begin = None
            j.begin(lines, name)

    def _ft_cmd_done(self, command: str):
        """Journal one COMPLETED command (record follows the fact) and
        auto-checkpoint every MRTPU_CKPT_EVERY commands.

        Also the command-round cancellation barrier and the generic
        post-command hook point: hooks run AFTER the journal/checkpoint
        landed (the serve/ mesh autoscaler promotes here — a clean
        host-side point between commands), then a cancelled request
        stops — with the checkpoint already durable, which is what
        leaves the session directory resumable at this exact boundary
        (doc/serve.md#deadlines-and-cancel)."""
        j = self._ft_journal
        if j is not None:
            self._ft_flush_begin()
            j.cmd_done(command)
            j.maybe_checkpoint(self.obj)
        for hook in list(self.post_cmd):
            try:
                hook(self)
            except Exception:
                # an observer hook must never kill the script it rides
                # (guarded remove: the hook may have removed itself
                # before raising)
                if hook in self.post_cmd:
                    self.post_cmd.remove(hook)
        from ..obs.context import barrier_check
        barrier_check()

    def _ft_apply_restore(self):
        rec, self._ft_restore = self._ft_restore, None
        if not rec:
            return
        ckpt, dir = rec
        from ..ft.journal import restore_mrs
        restore_mrs(self.obj, ckpt, dir)

    def cmd_resume(self, args):
        """resume <dir> — replay the op journal under <dir> from its
        last durable checkpoint into THIS interpreter (ft/journal.py;
        doc/reliability.md has the runbook)."""
        if len(args) != 1:
            raise MRError("Illegal resume command")
        from ..ft.journal import resume_into
        resume_into(self, args[0])

    def _run_registered(self, name: str, args: List[str]):
        """-i/-o switch split + params + run (input.cpp:429-468)."""
        iarg = 0
        while iarg < len(args) and args[iarg] not in ("-i", "-o"):
            iarg += 1
        params, rest = args[:iarg], args[iarg:]
        cmd = COMMANDS[name](self.obj, screen=self.screen
                             if self.screen is not None else False)
        cmd.params(params)
        i = 0
        ninput_args = 0
        while i < len(rest):
            if rest[i] == "-i":
                j = i + 1
                while j < len(rest) and rest[j] not in ("-i", "-o"):
                    j += 1
                for a in rest[i + 1:j]:
                    self._add_input(a)
                ninput_args += j - i - 1
                i = j
            elif rest[i] == "-o":
                j = i + 1
                while j < len(rest) and rest[j] not in ("-i", "-o"):
                    j += 1
                pairs = rest[i + 1:j]
                if len(pairs) % 2:
                    raise MRError("Invalid command switch: -o takes "
                                  "file/name pairs")
                for k in range(0, len(pairs), 2):
                    f, n = pairs[k], pairs[k + 1]
                    self.obj.add_output(
                        path=None if f == "NULL"
                        else self._expandpath(f, output=True),
                        mr_name=None if n == "NULL" else n)
                i = j
            else:
                raise MRError("Invalid command switch")
        # one arg per input descriptor, arity checked like the reference
        # (command.cpp:21-27 "Mismatch in command inputs") — silently
        # dropping extras hid a two-file `-i f1 f2` on a 1-input command
        # (r5 verify); a multi-file input goes through a v_name variable
        if ninput_args and ninput_args != cmd.ninputs:
            raise MRError(
                f"Mismatch in command inputs: {name} takes "
                f"{cmd.ninputs}, got {ninput_args} (use a v_name "
                f"variable for a multi-file input)")
        from ..obs import get_tracer
        t0 = _time.perf_counter()
        try:
            # every script command is one span (obs/): a script's trace
            # reads as oink.<command> parents over the MR-op spans
            with get_tracer().span(f"oink.{name}", cat="oink",
                                   args=" ".join(params)):
                cmd.run()
        finally:
            self.obj.cleanup()
        self.deltatime = _time.perf_counter() - t0

    def _expandpath(self, path: str, output: bool = False) -> str:
        """prepend + '%' substitution (reference expandpath,
        object.cpp:913-960): output paths always expand '%' to the proc
        id (0 under one controller); input paths only when `set
        substitute` is on."""
        if output or getattr(self, "_path_substitute", 0):
            path = path.replace("%", "0")
        pre = getattr(self, "_path_prepend", None)
        if pre:
            path = os.path.join(pre, path)
        return path

    def _add_input(self, arg: str):
        """-i arg: named MR, v_name multi-path variable (object.cpp
        add_input v_ handling, :450-462), or a path."""
        if arg in self.obj.named:
            self.obj.add_input(arg)
            return
        paths = expand_path_variable(self.variables, arg)
        if paths is not None:
            self.obj.add_input([self._expandpath(p) for p in paths])
            return
        self.obj.add_input(self._expandpath(arg))

    # ------------------------------------------------------------------
    # built-ins (reference input.cpp:497-796)
    # ------------------------------------------------------------------
    def cmd_clear(self, args):
        if args:
            raise MRError("Illegal clear command")
        self.obj.cleanup()
        for name in list(self.obj.named):
            self.obj.delete_mr(name)
        defaults = dict(self.obj.defaults)
        pinned = dict(self.obj.pinned)
        self.obj = ObjectManager(comm=self.obj.comm)
        # `set` defaults — and the serve/ tenant-budget pins — survive
        # a clear: a script-level clear must not be able to shed the
        # budget wiring the daemon seeded (doc/serve.md)
        self.obj.defaults.update(defaults)
        self.obj.pinned.update(pinned)
        self.dispatch = MRScriptDispatch(self.obj, self.variables)

    def cmd_echo(self, args):
        modes = {"none": (False, False), "screen": (True, False),
                 "log": (False, True), "both": (True, True)}
        if len(args) != 1 or args[0] not in modes:
            raise MRError("Illegal echo command")
        self.echo_screen, self.echo_log = modes[args[0]]

    def cmd_if(self, args):
        """if "bool" then "cmd" ... elif "bool" "cmd" ... else "cmd" ...
        (input.cpp:527-640; each command is a quoted full line)."""
        if len(args) < 3 or args[1] != "then":
            raise MRError("Illegal if command")

        def block_end(start):
            j = start
            while j < len(args) and args[j] not in ("elif", "else"):
                j += 1
            return j

        cond = self.variables.evaluate_boolean(self._substitute(args[0]))
        first, last = 2, block_end(2)
        while True:
            if cond != 0.0:
                cmds = args[first:last]
                if not cmds:
                    raise MRError("Illegal if command")
                for c in cmds:
                    self.one(c)
                return
            if last >= len(args):
                return
            if args[last] == "elif":
                if last + 2 > len(args):
                    raise MRError("Illegal if command")
                cond = self.variables.evaluate_boolean(
                    self._substitute(args[last + 1]))
                first = last + 2
            else:  # else
                cond = 1.0
                first = last + 1
            last = block_end(first)

    def cmd_include(self, args):
        if len(args) != 1:
            raise MRError("Illegal include command")
        self.run_file(args[0])

    def cmd_jump(self, args):
        if not 1 <= len(args) <= 2:
            raise MRError("Illegal jump command")
        if self._jump_skip:
            self._jump_skip = False
            return
        if len(args) == 2:
            self._label_active = True
            self._labelstr = args[1]
        if args[0] == "SELF":
            self._jump_to = ("SELF", None)
        else:
            with open(args[0]) as f:
                self._jump_to = (args[0], f.read().splitlines())

    def cmd_label(self, args):
        if len(args) != 1:
            raise MRError("Illegal label command")
        if self._label_active and self._labelstr == args[0]:
            self._label_active = False

    def cmd_log(self, args):
        if len(args) != 1:
            raise MRError("Illegal log command")
        if self.logfile:
            self.logfile.close()
        self.logfile = None if args[0] == "none" else open(args[0], "w")

    def cmd_next(self, args):
        if self.variables.next(args):
            self._jump_skip = True

    def cmd_print(self, args):
        if len(args) != 1:
            raise MRError("Illegal print command")
        self._emit(self._substitute(args[0]) + " \n")

    def cmd_shell(self, args):
        """The reference's deliberately-restricted verb set — cd/mkdir/
        mv/rm/rmdir via libc calls, never system() (input.cpp:751-791)."""
        if not args:
            raise MRError("Illegal shell command")
        verb = args[0]
        if verb == "cd":
            if len(args) != 2:
                raise MRError("Illegal shell command")
            os.chdir(args[1])
        elif verb == "mkdir":
            if len(args) < 2:
                raise MRError("Illegal shell command")
            for d in args[1:]:
                os.makedirs(d, exist_ok=True)
        elif verb == "mv":
            if len(args) != 3:
                raise MRError("Illegal shell command")
            shutil.move(args[1], args[2])
        elif verb == "rm":
            if len(args) < 2:
                raise MRError("Illegal shell command")
            for f in args[1:]:
                try:
                    os.unlink(f)
                except FileNotFoundError:
                    pass
        elif verb == "rmdir":
            if len(args) < 2:
                raise MRError("Illegal shell command")
            for d in args[1:]:
                try:
                    os.rmdir(d)
                except FileNotFoundError:
                    pass
        else:
            raise MRError("Illegal shell command")

    def cmd_variable(self, args):
        self.variables.set(args)

    # -- OINK object commands (input.cpp:799-831) --------------------------
    def cmd_mr(self, args):
        """mr ID [verbosity [timer [memsize [outofcore]]]]
        (object.cpp add_mr)."""
        if not 1 <= len(args) <= 5:
            raise MRError("Illegal mr command")
        name = args[0]
        if not all(c.isalnum() or c == "_" for c in name):
            raise MRError("MR ID must be alphanumeric or underscore "
                          "characters")
        if name in self.obj.named:
            raise MRError("ID in mr command is already in use")
        mr = self.obj.create_mr()
        for key, val in zip(("verbosity", "timer", "memsize", "outofcore"),
                            args[1:]):
            mr.set(**{key: int(val)})
        self.obj.name_mr(name, mr)

    def cmd_set(self, args):
        """set keyword value ... (object.cpp Object::set).  `scratch`
        maps to our fpath spill-dir setting; `prepend`/`substitute`
        shape -i/-o path resolution (expandpath, object.cpp:913-960)."""
        if len(args) % 2:
            raise MRError("Illegal set command")
        for i in range(0, len(args), 2):
            key, val = args[i], args[i + 1]
            if key == "scratch":
                self.obj.set_default("fpath", val)
            elif key == "onfault":
                # string-valued ft/ policy (fail|retry|skip)
                self.obj.set_default("onfault", val)
            elif key == "prepend":
                root = getattr(self, "_path_root", None)
                if root is not None:
                    # serve/ sessions anchor ALL relative output under
                    # their own directory: the script's prepend idiom
                    # keeps working, re-rooted inside the sandbox; an
                    # absolute prepend would silently move -o files
                    # out of the session (losing them from the result
                    # and the crash-replay golden), so it fails loudly
                    if os.path.isabs(val):
                        raise MRError(
                            "absolute prepend is pinned by the server "
                            "(session outputs stay in the session "
                            "directory; doc/serve.md)")
                    val = os.path.join(root, val)
                self._path_prepend = val
            elif key == "substitute":
                self._path_substitute = int(val)
            else:
                self.obj.set_default(key, int(val))

    def cmd_input(self, args):
        """input N keyword value ... — per-slot descriptor settings.  We
        accept and store them; only 'prepend'/'substitute' alter path
        resolution here (reference object.cpp user_input's full set
        drives the byte-chunk map variants)."""
        if len(args) < 3:
            raise MRError("Illegal input command")
        self.obj.user_input_settings = getattr(
            self.obj, "user_input_settings", {})
        self.obj.user_input_settings[int(args[0])] = dict(
            zip(args[1::2], args[2::2]))

    def cmd_output(self, args):
        if len(args) < 3:
            raise MRError("Illegal output command")
        self.obj.user_output_settings = getattr(
            self.obj, "user_output_settings", {})
        self.obj.user_output_settings[int(args[0])] = dict(
            zip(args[1::2], args[2::2]))


# ---------------------------------------------------------------------------
# line chopping helpers (reference Input::parse)
# ---------------------------------------------------------------------------

def _strip_comment(line: str) -> str:
    quote = ""
    for i, c in enumerate(line):
        if c == "#" and not quote:
            return line[:i]
        if quote and c == quote:
            quote = ""
        elif not quote and c in "\"'":
            quote = c
    return line


def _split_args(line: str) -> List[str]:
    """Whitespace split with single/double-quoted strings as one arg
    (input.cpp:289-321)."""
    out: List[str] = []
    i, n = 0, len(line)
    while i < n:
        while i < n and line[i].isspace():
            i += 1
        if i >= n:
            break
        if line[i] in "\"'":
            q = line[i]
            j = line.find(q, i + 1)
            if j < 0:
                raise MRError("Unbalanced quotes in input line")
            out.append(line[i + 1:j])
            i = j + 1
        else:
            j = i
            while j < n and not line[j].isspace():
                j += 1
            out.append(line[i:j])
            i = j
    return out


# ---------------------------------------------------------------------------
# command line front end (reference oink/oink.cpp switches + main.cpp)
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    """oink-style driver: ``python -m gpu_mapreduce_tpu.oink.script
    [-in file] [-log file|none] [-screen file|none] [-echo style]
    [-partition NxM ...] [-var name value...]``
    (reference oink.cpp:45-125)."""
    argv = list(sys.argv[1:] if argv is None else argv)
    infile = None
    logname: Optional[str] = "log.oink"
    lograw: Optional[str] = None      # the explicit -log value, if any
    screen: object = None
    screenraw: Optional[str] = None
    echo = None
    varsets = []
    partition: List[str] = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a in ("-in", "-i"):
            infile = argv[i + 1]
            i += 2
        elif a in ("-log", "-l"):
            lograw = argv[i + 1]
            logname = None if lograw == "none" else lograw
            i += 2
        elif a in ("-screen", "-sc"):
            screenraw = argv[i + 1]
            i += 2
        elif a in ("-echo", "-e"):
            echo = argv[i + 1]
            i += 2
        elif a in ("-partition", "-p"):
            i += 1
            while i < len(argv) and not argv[i].startswith("-"):
                partition.append(argv[i])
                i += 1
            if not partition:
                raise SystemExit("Invalid command-line argument: "
                                 "-partition needs world specs")
        elif a in ("-var", "-v"):
            name = argv[i + 1]
            vals = []
            i += 2
            while i < len(argv) and not argv[i].startswith("-"):
                vals.append(argv[i])
                i += 1
            varsets.append((name, vals))
        else:
            raise SystemExit(f"Invalid command-line argument: {a}")
    if partition:
        # multi-world run (reference oink.cpp:99-100 requires -in)
        if not infile:
            raise SystemExit("Must use -in switch with multiple partitions")
        from .universe import Universe, run_universe

        # the reference gets its proc count from mpirun; ours comes from
        # the visible device list — build a mesh exactly as large as the
        # partition specs demand (worlds then split it)
        probe = Universe(0)
        for spec in partition:
            probe.add_world(spec)
        total = sum(probe.procs_per_world)
        if total <= 1:
            comm = None
        else:
            import jax

            from ..parallel.mesh import make_mesh
            if len(jax.devices()) < total:
                raise SystemExit(
                    f"Processor partitions are inconsistent: specs need "
                    f"{total} procs, {len(jax.devices())} devices visible")
            comm = make_mesh(total)
        run_universe(infile, partition, comm=comm, logname=lograw,
                     screenname=screenraw, echo=echo, varsets=varsets)
        return 0
    if screenraw is not None:
        screen = False if screenraw == "none" else open(screenraw, "w")
    interp = OinkScript(screen=screen, logfile=logname)
    if echo:
        interp.cmd_echo([echo])
    for name, vals in varsets:
        interp.variables.set([name, "index"] + vals)
    try:
        if infile:
            interp.run_file(infile)
        else:
            interp.run_string(sys.stdin.read())
    finally:
        interp.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
