"""Lightweight YAML config composition engine (Hydra-equivalent surface).

The reference uses Hydra 1.3 (sheeprl/configs/config.yaml, @hydra.main on
sheeprl/cli.py:358). Hydra is torch-free but not available in this image, so this module
re-implements the subset the framework needs, with the same UX:

- a config tree ``sheeprl_tpu/configs/<group>/<option>.yaml`` composed via ``defaults:``
  lists (group selection, ``/group@key`` placement, ``override /group: option``),
- experiment overlays (``exp=dreamer_v3_100k_ms_pacman``) merged at global scope,
- ``${a.b.c}`` interpolation over the merged tree (plus ``${eval:...}`` arithmetic),
- CLI dotlist overrides (``algo.mlp_keys.encoder=[state]``, group swaps ``algo=sac``),
- ``_target_`` instantiation (hydra.utils.instantiate equivalent),
- an extra-search-path hook via the ``SHEEPRL_SEARCH_PATH`` env var
  (reference: hydra_plugins/sheeprl_search_path.py:11-33).
"""

from __future__ import annotations

import copy
import importlib
import os
import re
import warnings
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import yaml

from sheeprl_tpu.core.compile import setup_phase
from sheeprl_tpu.utils.utils import dotdict, get_nested, set_nested

MISSING = "???"

_PKG_CONFIG_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")


class ConfigError(RuntimeError):
    pass


def _search_dirs(extra: Optional[Sequence[str]] = None) -> List[str]:
    dirs = list(extra or [])
    env = os.environ.get("SHEEPRL_SEARCH_PATH", "")
    for entry in env.split(";"):
        entry = entry.strip()
        if not entry:
            continue
        # accept hydra-style "file://path" entries for parity with the reference plugin
        entry = re.sub(r"^file://", "", entry)
        dirs.append(entry)
    dirs.append(_PKG_CONFIG_DIR)
    return dirs


def _find_yaml(rel: str, search: Sequence[str]) -> Optional[str]:
    for base in search:
        for ext in (".yaml", ".yml"):
            path = os.path.join(base, rel + ext)
            if os.path.isfile(path):
                return path
    return None


class _ConfigLoader(yaml.SafeLoader):
    """SafeLoader that also parses scientific notation without a dot (1e-3) as float."""


_ConfigLoader.add_implicit_resolver(
    "tag:yaml.org,2002:float",
    re.compile(
        r"""^(?:[-+]?(?:[0-9][0-9_]*)\.[0-9_]*(?:[eE][-+]?[0-9]+)?
        |[-+]?(?:[0-9][0-9_]*)(?:[eE][-+]?[0-9]+)
        |\.[0-9_]+(?:[eE][-+][0-9]+)?
        |[-+]?\.(?:inf|Inf|INF)
        |\.(?:nan|NaN|NAN))$""",
        re.X,
    ),
    list("-+0123456789."),
)


def _yaml_load(stream):
    return yaml.load(stream, Loader=_ConfigLoader)


def _load_yaml(path: str) -> Dict[str, Any]:
    with open(path) as f:
        data = _yaml_load(f) or {}
    if not isinstance(data, dict):
        raise ConfigError(f"Config file {path} must contain a mapping, got {type(data)}")
    return data


def _deep_merge(dst: Dict[str, Any], src: Mapping[str, Any]) -> Dict[str, Any]:
    """Merge ``src`` into ``dst`` in place. Dicts merge recursively; others overwrite."""
    for key, value in src.items():
        if key in dst and isinstance(dst[key], dict) and isinstance(value, Mapping):
            _deep_merge(dst[key], value)
        else:
            dst[key] = copy.deepcopy(value) if isinstance(value, (dict, list)) else value
    return dst


def group_exists(group: str, extra_search: Optional[Sequence[str]] = None) -> bool:
    return any(os.path.isdir(os.path.join(base, group)) for base in _search_dirs(extra_search))


def _parse_defaults_entry(entry: Any) -> Tuple[str, Optional[str], bool]:
    """Return ``(group_path_with_at, option, is_override)`` for a defaults-list entry."""
    if isinstance(entry, str):
        return entry, None, False
    if isinstance(entry, Mapping) and len(entry) == 1:
        key, value = next(iter(entry.items()))
        key = str(key).strip()
        override = False
        if key.startswith("override "):
            override = True
            key = key[len("override "):].strip()
        return key, (None if value is None else str(value)), override
    raise ConfigError(f"Malformed defaults entry: {entry!r}")


def _compose_file(
    path: str,
    search: Sequence[str],
    selections: Dict[str, str],
    group_prefix: str = "",
    consumed: Optional[set] = None,
    mounted: Optional[set] = None,
) -> Dict[str, Any]:
    """Compose one yaml file: process its defaults list, then merge its own body.

    ``group_prefix`` is the group dir of the file itself, so relative defaults entries
    (e.g. ``- ppo`` inside ``algo/a2c.yaml``) resolve within the same group.
    ``consumed`` (when given) collects the ``group@package`` selection keys that
    matched a mount, so compose() can reject typo'd packages instead of silently
    ignoring them; ``mounted`` collects the group names whose mounts were actually
    encountered, so a selection addressing a mount that legitimately never composed
    (enclosing group null/absent) warns instead of erroring.
    """
    raw = _load_yaml(path)
    defaults = raw.pop("defaults", None)
    composed: Dict[str, Any] = {}
    self_merged = False

    if defaults is not None:
        if not isinstance(defaults, list):
            raise ConfigError(f"'defaults' in {path} must be a list")
        for entry in defaults:
            key, option, is_override = _parse_defaults_entry(entry)
            if key == "_self_":
                _deep_merge(composed, raw)
                self_merged = True
                continue
            # split group@placement
            if "@" in key:
                group_part, placement = key.split("@", 1)
            else:
                group_part, placement = key, None
            group_part = group_part.strip()
            absolute = group_part.startswith("/")
            group_rel = group_part.lstrip("/")
            if option is None and "/" not in group_rel and placement is None and not absolute:
                # bare include of a sibling file: "- ppo" inside algo/
                rel = os.path.join(group_prefix, group_rel) if group_prefix else group_rel
                sub_path = _find_yaml(rel, search)
                if sub_path is None:
                    raise ConfigError(f"Cannot find base config '{rel}' (from {path})")
                _deep_merge(composed, _compose_file(sub_path, search, selections, group_prefix, consumed, mounted))
                continue
            group = group_rel if absolute or not group_prefix else os.path.join(group_prefix, group_rel)
            if is_override:
                # overrides from overlays replace the *top-level* selection
                selections[group_rel] = option if option is not None else selections.get(group_rel)
                continue
            # CLI group selections win over the file's default option. A
            # "group@package=option" override matches only the mount whose
            # effective package (file's mount point + local placement) agrees;
            # a bare "group=option" selection re-points every mount.
            local_pkg = placement if placement is not None else group_rel.split("/")[-1]
            eff_pkg = f"{group_prefix}.{local_pkg}" if group_prefix else local_pkg
            pkg_key = f"{group_rel}@{eff_pkg}"
            if mounted is not None:
                mounted.add(group_rel)
            if pkg_key in selections:
                option = selections[pkg_key]
                if consumed is not None:
                    consumed.add(pkg_key)
            else:
                option = selections.get(group_rel, option)
            if option in (None, "null"):
                continue
            if option == MISSING:
                selections.setdefault(group_rel, MISSING)
                continue
            rel = os.path.join(group, option)
            sub_path = _find_yaml(rel, search)
            if sub_path is None:
                raise ConfigError(f"Cannot find config '{rel}' referenced from {path}")
            sub_cfg = _compose_file(sub_path, search, selections, os.path.dirname(rel), consumed, mounted)
            target_key = placement if placement is not None else group_rel.split("/")[-1]
            if target_key in ("_global_", "_here_", ""):
                _deep_merge(composed, sub_cfg)
            else:
                node = composed
                parts = target_key.split(".")
                for part in parts[:-1]:
                    node = node.setdefault(part, {})
                if parts[-1] in node and isinstance(node[parts[-1]], dict):
                    _deep_merge(node[parts[-1]], sub_cfg)
                else:
                    node[parts[-1]] = sub_cfg

    if not self_merged:
        _deep_merge(composed, raw)
    return composed


_INTERP_RE = re.compile(r"\$\{([^${}]+)\}")


def _resolve_value(expr: str, root: Mapping[str, Any]):
    expr = expr.strip()
    if expr.startswith("now:"):
        import datetime

        return datetime.datetime.now().strftime(expr[4:])
    if expr.startswith("env:"):
        parts = expr[4:].split(",", 1)
        return os.environ.get(parts[0].strip(), parts[1].strip() if len(parts) > 1 else None)
    if expr.startswith("eval:"):
        body = expr[5:]
        return eval(body, {"__builtins__": {}}, {"min": min, "max": max, "int": int, "float": float, "abs": abs})
    sentinel = object()
    value = get_nested(root, expr, sentinel)
    if value is sentinel:
        raise ConfigError(f"Interpolation '${{{expr}}}' does not resolve")
    return value


def resolve_interpolations(cfg: Dict[str, Any], max_passes: int = 20) -> Dict[str, Any]:
    """Resolve ``${...}`` references in all string leaves, iterating to a fixpoint."""

    def visit(node, root):
        if isinstance(node, dict):
            return {k: visit(v, root) for k, v in node.items()}
        if isinstance(node, list):
            return [visit(v, root) for v in node]
        if isinstance(node, str) and "${" in node:
            full = _INTERP_RE.fullmatch(node.strip())
            if full:
                return _resolve_value(full.group(1), root)

            def sub(m):
                v = _resolve_value(m.group(1), root)
                return str(v)

            return _INTERP_RE.sub(sub, node)
        return node

    for _ in range(max_passes):
        new = visit(cfg, cfg)
        if new == cfg:
            return new
        cfg = new
    # one more pass to surface unresolvable refs
    return visit(cfg, cfg)


def _parse_cli_value(text: str):
    try:
        return _yaml_load(text)
    except yaml.YAMLError:
        return text


@setup_phase("compose")
def compose(
    config_name: str = "config",
    overrides: Optional[Sequence[str]] = None,
    config_dirs: Optional[Sequence[str]] = None,
) -> dotdict:
    """Compose the full config: root file + group selections + CLI overrides."""
    overrides = list(overrides or [])
    search = _search_dirs(config_dirs)

    root_path = _find_yaml(config_name, search)
    if root_path is None:
        raise ConfigError(f"Root config '{config_name}' not found in {search}")

    raw_root = _load_yaml(root_path)
    defaults = raw_root.get("defaults", [])

    # Partition CLI overrides into group selections vs dotted value overrides.
    selections: Dict[str, str] = {}
    dotted: List[Tuple[str, Any]] = []
    for ov in overrides:
        if "=" not in ov:
            raise ConfigError(f"Override '{ov}' must look like key=value")
        key, _, value = ov.partition("=")
        key = key.strip().lstrip("+")
        value = value.strip()
        if "@" in key:
            # hydra's "group@package=option" (e.g. logger@metric.logger=mlflow):
            # selects an option for the group AT THAT PACKAGE ONLY — other mounts
            # of the same group keep their defaults (selection key carries the
            # package, consulted by _compose_file against each mount's location)
            group, package = key.split("@", 1)
            group = group.lstrip("/")
            if not group_exists(group, config_dirs):
                raise ConfigError(f"Override '{ov}': unknown config group '{group}'")
            selections[f"{group}@{package}"] = value
            continue
        is_group = ("." not in key) and group_exists(key, config_dirs) and not isinstance(
            _parse_cli_value(value), (dict, list)
        )
        # "group.sub=opt" group selection (e.g. env=minecraft/navigate) handled via '/'
        if is_group:
            selections[key] = value
        else:
            dotted.append((key, _parse_cli_value(value)))

    # First pass over root defaults collects the default selection per group.
    base_selections: Dict[str, str] = {}
    ordered_groups: List[Tuple[str, Optional[str]]] = []  # (group, placement)
    for entry in defaults:
        key, option, _ = _parse_defaults_entry(entry)
        if key == "_self_":
            ordered_groups.append(("_self_", None))
            continue
        if "@" in key:
            group, placement = key.split("@", 1)
        else:
            group, placement = key, None
        group = group.lstrip("/")
        ordered_groups.append((group, placement))
        if option is not None:
            base_selections[group] = option

    # Overlay (exp) files may carry their own "override /group: option" directives.
    # Compose overlays first to harvest those, then build the tree in root order.
    harvested: Dict[str, str] = dict(base_selections)
    for group, sel in selections.items():
        harvested[group] = sel

    consumed_pkgs: set = set()
    mounted_groups: set = {g for g, _ in ordered_groups if g != "_self_"}

    def _root_mount_selection(group: str, placement: Optional[str], current):
        """Honor (and mark consumed) a package-scoped CLI selection addressing a
        ROOT-defaults mount of ``group`` (e.g. the Hydra-valid ``algo@algo=sac``)."""
        pkg_key = f"{group}@{placement if placement is not None else group.split('/')[-1]}"
        if pkg_key in selections:
            consumed_pkgs.add(pkg_key)
            return selections[pkg_key]
        return current

    overlay_cfgs: Dict[str, Dict[str, Any]] = {}
    # exp (and any group whose file uses @_global_ packaging) must be able to override
    # other groups, so compose them first.
    for group, placement in ordered_groups:
        if group == "_self_":
            continue
        option = _root_mount_selection(group, placement, harvested.get(group))
        if option in (None, "null"):
            continue
        if option == MISSING:
            continue
        rel = os.path.join(group, str(option))
        path = _find_yaml(rel, search)
        if path is None:
            raise ConfigError(f"Cannot find config '{rel}'. Available search path: {search}")
        # seed with CLI selections so nested group mounts (e.g. metric/default.yaml's
        # "/logger@logger") honor "group@package=option" overrides
        sub_sel: Dict[str, str] = dict(selections)
        cfg_piece = _compose_file(path, search, sub_sel, group, consumed_pkgs, mounted_groups)
        overlay_cfgs[group] = cfg_piece
        for g, o in sub_sel.items():
            if o is not None and g not in selections:  # CLI wins over overlay overrides
                harvested[g] = o
                # re-compose that group with the overlay's selection
                overlay_cfgs.pop(g, None)

    # Second pass: compose every group with final selections, in root-defaults order.
    cfg: Dict[str, Any] = {}
    for group, placement in ordered_groups:
        if group == "_self_":
            body = {k: v for k, v in raw_root.items() if k != "defaults"}
            _deep_merge(cfg, body)
            continue
        option = _root_mount_selection(group, placement, harvested.get(group))
        if option in (None, "null"):
            continue
        if option == MISSING:
            raise ConfigError(
                f"You must specify '{group}', e.g. '{group}=default' (missing mandatory group)"
            )
        rel = os.path.join(group, str(option))
        path = _find_yaml(rel, search)
        if path is None:
            raise ConfigError(f"Cannot find config '{rel}' for {group}={option}")
        cfg_piece = overlay_cfgs.get(group)
        if cfg_piece is None:
            cfg_piece = _compose_file(path, search, dict(selections), group, consumed_pkgs, mounted_groups)
        target_key = placement if placement is not None else group.split("/")[-1]
        if _is_global_packaged(path):
            _deep_merge(cfg, cfg_piece)
            cfg.pop("_global_", None)
        elif target_key in ("_global_",):
            _deep_merge(cfg, cfg_piece)
        else:
            if target_key in cfg and isinstance(cfg[target_key], dict):
                _deep_merge(cfg[target_key], cfg_piece)
            else:
                cfg[target_key] = cfg_piece
        # record which option was chosen (useful for checkpoints/debug)
        cfg.setdefault("_groups_", {})[group] = option

    # Reject package-scoped selections that matched no mount (silent typos:
    # "logger@metric.loger=mlflow" would otherwise leave the default in place).
    # If NO mount of the group was composed at all, the selection may merely be
    # inactive (its enclosing group selected to null or an option that omits the
    # mount) — warn instead of erroring, matching Hydra's tolerance.
    for sel_key in selections:
        if "@" in sel_key and sel_key not in consumed_pkgs:
            group, package = sel_key.split("@", 1)
            if group in mounted_groups:
                raise ConfigError(
                    f"Override '{sel_key}={selections[sel_key]}' matched no mount of group "
                    f"'{group}' at package '{package}' (check the package path)"
                )
            warnings.warn(
                f"Override '{sel_key}={selections[sel_key]}' addressed group '{group}' "
                f"but no mount of that group was composed (inactive mount?); ignoring",
                stacklevel=2,
            )

    # Dotted overrides, after composition.
    for key, value in dotted:
        set_nested(cfg, key, value)

    cfg = resolve_interpolations(cfg)
    _check_missing(cfg, "")
    return dotdict(cfg)


def _is_global_packaged(path: str) -> bool:
    """Detect the '# @package _global_' marker used by exp overlay files."""
    try:
        with open(path) as f:
            for _ in range(3):
                line = f.readline()
                if "@package" in line and "_global_" in line:
                    return True
    except OSError:
        pass
    return False


def _check_missing(node: Any, prefix: str) -> None:
    if isinstance(node, dict):
        for k, v in node.items():
            _check_missing(v, f"{prefix}{k}.")
    elif isinstance(node, list):
        for i, v in enumerate(node):
            _check_missing(v, f"{prefix}{i}.")
    elif node == MISSING:
        raise ConfigError(f"Missing mandatory value: {prefix[:-1]}")


def load_config(overrides: Optional[Sequence[str]] = None, config_name: str = "config") -> dotdict:
    return compose(config_name=config_name, overrides=overrides)


def instantiate(spec: Mapping[str, Any], *args, **kwargs):
    """``hydra.utils.instantiate`` equivalent: import ``_target_`` and call it.

    Nested dicts with ``_target_`` are instantiated recursively unless
    ``_partial_: true`` (returns a partial) or ``_args_`` present.
    """
    import functools

    if not isinstance(spec, Mapping) or "_target_" not in spec:
        raise ConfigError(f"instantiate() needs a mapping with '_target_', got {spec!r}")
    target = spec["_target_"]
    module_name, _, attr = target.rpartition(".")
    try:
        obj = getattr(importlib.import_module(module_name), attr)
    except (ImportError, AttributeError) as e:
        raise ConfigError(f"Cannot import '{target}': {e}") from e

    call_kwargs: Dict[str, Any] = {}
    for key, value in spec.items():
        if key in ("_target_", "_partial_", "_args_", "_convert_"):
            continue
        if isinstance(value, Mapping) and "_target_" in value:
            value = instantiate(value)
        call_kwargs[key] = value
    call_kwargs.update(kwargs)
    call_args = list(spec.get("_args_", [])) + list(args)
    if spec.get("_partial_", False):
        return functools.partial(obj, *call_args, **call_kwargs)
    return obj(*call_args, **call_kwargs)
