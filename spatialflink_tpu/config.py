"""YAML config system (reference: ``utils/Params.java:74-489`` +
``utils/ConfigType.java`` + ``conf/geoflink-conf.yml``).

The reference loads a snakeyaml POJO and null-checks every field with typed
exceptions; here the same schema is parsed into dataclasses with explicit
validation errors naming the offending key. The YAML key names are kept
byte-identical to the reference's so an existing ``geoflink-conf.yml`` drops
in unchanged (the leading ``!!GeoFlink.utils.ConfigType`` java type tag is
tolerated and stripped).
"""

from __future__ import annotations

import dataclasses
import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from spatialflink_tpu.index import UniformGrid
from spatialflink_tpu.models import LineString, Point, Polygon

SUPPORTED_FORMATS = ("GeoJSON", "WKT", "CSV", "TSV")
SUPPORTED_AGGREGATES = ("ALL", "SUM", "AVG", "MIN", "MAX", "COUNT")
SUPPORTED_WINDOW_TYPES = ("TIME", "COUNT")


class ConfigError(ValueError):
    """Raised on a missing/invalid config field (the reference throws
    ``NullPointerException``/``IllegalArgumentException`` per field,
    ``utils/Params.java:100-489``)."""


def _req(d: Dict[str, Any], key: str, where: str):
    if key not in d or d[key] is None:
        raise ConfigError(f"{where}: missing required key {key!r}")
    return d[key]


def _opt(d: Dict[str, Any], key: str, default):
    v = d.get(key)
    return default if v is None else v


def _normalize_delimiter(v: str) -> str:
    # the reference conf writes TSV delimiters as a literal TAB, "\t", or
    # "\\\\t" (conf/geoflink-conf.yml:24,40); all map to TAB
    if v in ("\\t", "\\\\t", "\t"):
        return "\t"
    return v


def _coord_pairs(v) -> List[Tuple[float, float]]:
    """queryPoints: YAML list of [x, y] pairs, or the reference's CLI
    bracket-string form '"[116.5, 40.5], [117.0, 40.7]"'
    (``HelperClass.getCoordinates``, :145-161)."""
    if isinstance(v, str):
        from spatialflink_tpu.streams.formats import parse_bracket_coords

        return parse_bracket_coords(v)
    return [tuple(map(float, p)) for p in v]


def _coord_lists(v) -> List[List[Tuple[float, float]]]:
    """queryPolygons/queryLineStrings: YAML nested lists, or the CLI
    bracket-string form '"[[x, y], ...], [[x, y], ...]"'
    (``HelperClass.getListCoordinates``, :163-179) — each group is one
    polygon ring / linestring."""
    if isinstance(v, str):
        from spatialflink_tpu.streams.formats import parse_bracket_rings

        return parse_bracket_rings(v)
    return [[tuple(map(float, c)) for c in grp] for grp in v]


@dataclass
class StreamConfig:
    """One ``inputStream{1,2}`` block (``utils/ConfigType.java:20-40``)."""

    topic_name: str = ""
    format: str = "GeoJSON"
    date_format: Optional[str] = "%Y-%m-%d %H:%M:%S"
    geojson_obj_id_attr: str = "oID"
    geojson_timestamp_attr: str = "timestamp"
    csv_tsv_schema: Sequence[int] = (0, 1, 2, 3)
    grid_bbox: Tuple[float, float, float, float] = (0.0, 0.0, 1.0, 1.0)
    num_grid_cells: int = 100
    cell_length: float = 0.0
    delimiter: str = ","
    charset: str = "UTF-8"

    def geojson_kwargs(self) -> dict:
        """GeoJSON parser kwargs — the single source shared by the record
        path (driver.decode_stream) and the columnar chunk decodes, so a
        renamed/added attribute cannot let them diverge."""
        return {"property_obj_id": self.geojson_obj_id_attr,
                "property_timestamp": self.geojson_timestamp_attr,
                "date_format": self.date_format}

    @classmethod
    def from_dict(cls, d: Dict[str, Any], where: str) -> "StreamConfig":
        fmt = str(_req(d, "format", where))
        if fmt not in SUPPORTED_FORMATS:
            raise ConfigError(
                f"{where}.format: {fmt!r} not in {SUPPORTED_FORMATS}")
        bbox = _req(d, "gridBBox", where)
        if len(bbox) != 4:
            raise ConfigError(f"{where}.gridBBox: need [minX, minY, maxX, maxY]")
        num_cells = int(_opt(d, "numGridCells", 0))
        cell_len = float(_opt(d, "cellLength", 0.0))
        if num_cells <= 0 and cell_len <= 0:
            raise ConfigError(
                f"{where}: one of numGridCells/cellLength must be positive")
        gj = list(_opt(d, "geoJSONSchemaAttr", ["oID", "timestamp"]))
        schema = [int(i) for i in _opt(d, "csvTsvSchemaAttr", [0, 1, 2, 3])]
        date_fmt = _java_date_format_to_python(
            _opt(d, "dateFormat", "yyyy-MM-dd HH:mm:ss"))
        return cls(
            topic_name=str(_req(d, "topicName", where)),
            format=fmt,
            date_format=date_fmt,
            geojson_obj_id_attr=gj[0] if gj else "oID",
            geojson_timestamp_attr=gj[1] if len(gj) > 1 else "timestamp",
            csv_tsv_schema=schema,
            grid_bbox=(float(bbox[0]), float(bbox[1]),
                       float(bbox[2]), float(bbox[3])),
            num_grid_cells=num_cells,
            cell_length=cell_len,
            delimiter=_normalize_delimiter(str(_opt(d, "delimiter", ","))),
            charset=str(_opt(d, "charset", "UTF-8")),
        )

    def make_grid(self) -> UniformGrid:
        """Grid per the stream's bbox — cellLength (meters-style) takes
        precedence when positive, like ``StreamingJob.java:309-315``."""
        min_x, min_y, max_x, max_y = self.grid_bbox
        if self.cell_length > 0:
            return UniformGrid(min_x, max_x, min_y, max_y,
                               cell_length=self.cell_length)
        return UniformGrid(min_x, max_x, min_y, max_y,
                           num_grid_partitions=self.num_grid_cells)


def _java_date_format_to_python(fmt: Optional[str]) -> Optional[str]:
    """yyyy-MM-dd HH:mm:ss → %Y-%m-%d %H:%M:%S (SimpleDateFormat subset)."""
    if not fmt:
        return None
    table = [
        ("yyyy", "%Y"), ("yy", "%y"), ("MM", "%m"), ("dd", "%d"),
        ("HH", "%H"), ("mm", "%M"), ("ss", "%S"), ("SSS", "%f"),
    ]
    out = str(fmt)
    for j, p in table:
        out = out.replace(j, p)
    return out


@dataclass
class OutputStreamConfig:
    topic_name: str = "output"
    delimiter: str = ","

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "OutputStreamConfig":
        return cls(
            topic_name=str(_opt(d, "topicName", "output")),
            delimiter=_normalize_delimiter(str(_opt(d, "delimiter", ","))),
        )


@dataclass
class QueryConfig:
    """``query:`` block (``conf/geoflink-conf.yml:49-72``)."""

    option: int = 1
    approximate: bool = False
    # answer ALL configured query points/geometries in one dispatch per
    # window (run_multi — TPU-native extension; the reference uses only the
    # FIRST query object, one query per job). Opt-in to preserve that
    # reference parity by default.
    multi_query: bool = False
    # device-mesh width for distributed window evaluation — the TPU analogue
    # of the reference's task parallelism (``env.setParallelism(30)``,
    # StreamingJob.java:221). 0/1 = single device.
    parallelism: int = 0
    # outer (DCN) axis width for multi-host runs: hosts > 1 makes the mesh
    # 2-D (hosts x parallelism/hosts) with two-level ICI->DCN merges; must
    # divide parallelism. 0/1 = flat 1-D mesh.
    hosts: int = 0
    # pane-incremental sliding-window execution (the --panes driver switch):
    # kernel partials computed once per slide-aligned pane and merged across
    # overlapping windows. Execution knob only — results are identical to
    # full-window evaluation (and tumbling/undecomposable specs bypass it).
    panes: bool = False
    # device-resident pane state (the --pane-merge driver switch): pane
    # partials stay in device memory across slides and windows merge them
    # on device, reading back only the sealed window's merged result.
    # Execution knob only — identical results; None = auto (device on
    # accelerator backends, host on CPU), False = host merge (the A/B the
    # pane-state bench row measures).
    pane_device_merge: Optional[bool] = None
    radius: float = 0.0
    aggregate_function: str = "SUM"
    k: int = 10
    omega_duration_s: int = 10
    traj_ids: List[str] = field(default_factory=list)
    query_points: List[Tuple[float, float]] = field(default_factory=list)
    query_polygons: List[List[Tuple[float, float]]] = field(default_factory=list)
    query_linestrings: List[List[Tuple[float, float]]] = field(default_factory=list)
    traj_deletion_threshold_s: int = 0
    allowed_lateness_s: int = 0

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "QueryConfig":
        agg = str(_opt(d, "aggregateFunction", "SUM")).upper()
        if agg not in SUPPORTED_AGGREGATES:
            raise ConfigError(
                f"query.aggregateFunction: {agg!r} not in {SUPPORTED_AGGREGATES}")
        th = _opt(d, "thresholds", {})
        parallelism = int(_opt(d, "parallelism", 0))
        if parallelism < 0 or (parallelism & (parallelism - 1)):
            raise ConfigError(
                "query.parallelism: must be 0 (off) or a power of two "
                "(window batch capacities are power-of-two buckets; the "
                "point dim must divide evenly across the mesh)")
        hosts = int(_opt(d, "hosts", 0))
        if hosts < 0 or (hosts & (hosts - 1)):
            raise ConfigError("query.hosts: must be 0 (off) or a power of two")
        # hosts-divides-parallelism is checked AFTER CLI overrides (driver
        # applies --devices/--hosts on top of the YAML; validate_mesh) and
        # again in the operator ctor as the backstop
        return cls(
            option=int(_req(d, "option", "query")),
            approximate=bool(_opt(d, "approximate", False)),
            multi_query=bool(_opt(d, "multiQuery", False)),
            parallelism=parallelism,
            hosts=hosts,
            panes=bool(_opt(d, "panes", False)),
            pane_device_merge=(None if _opt(d, "paneDeviceMerge", None)
                               is None
                               else bool(_opt(d, "paneDeviceMerge", None))),
            radius=float(_opt(d, "radius", 0.0)),
            aggregate_function=agg,
            k=int(_opt(d, "k", 10)),
            omega_duration_s=int(_opt(d, "omegaDuration", 10)),
            traj_ids=[str(t) for t in _opt(d, "trajIDs", [])],
            query_points=_coord_pairs(_opt(d, "queryPoints", [])),
            query_polygons=_coord_lists(_opt(d, "queryPolygons", [])),
            query_linestrings=_coord_lists(_opt(d, "queryLineStrings", [])),
            traj_deletion_threshold_s=int(_opt(th, "trajDeletion", 0)),
            allowed_lateness_s=int(_opt(th, "outOfOrderTuples", 0)),
        )


@dataclass
class WindowConfig:
    """``window:`` block — TIME windows in seconds (``geoflink-conf.yml:74-78``)."""

    type: str = "TIME"
    interval_s: float = 5.0
    step_s: float = 5.0

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "WindowConfig":
        wt = str(_opt(d, "type", "TIME")).upper()
        if wt not in SUPPORTED_WINDOW_TYPES:
            raise ConfigError(
                f"window.type: {wt!r} not in {SUPPORTED_WINDOW_TYPES}")
        interval = float(_req(d, "interval", "window"))
        step = float(_opt(d, "step", interval))
        if interval <= 0 or step <= 0:
            raise ConfigError("window.interval/step must be positive")
        return cls(type=wt, interval_s=interval, step_s=step)


@dataclass
class Params:
    """Validated full config (``utils/Params.java``)."""

    cluster_mode: bool = False
    kafka_bootstrap_servers: str = "localhost:9092"
    input1: StreamConfig = field(default_factory=StreamConfig)
    input2: StreamConfig = field(default_factory=StreamConfig)
    output: OutputStreamConfig = field(default_factory=OutputStreamConfig)
    query: QueryConfig = field(default_factory=QueryConfig)
    window: WindowConfig = field(default_factory=WindowConfig)
    # CLI-only knobs (no YAML field in the reference schema): state
    # checkpointing for stateful realtime queries
    checkpoint_path: Optional[str] = None
    checkpoint_every: int = 16
    # job fingerprint stored in (and verified against) checkpoint meta so a
    # resume under a different query/window config refuses instead of
    # producing wrong state; set by the driver from job_fingerprint()
    checkpoint_job: Optional[str] = None

    # ------------------------------------------------------------------ #

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Params":
        in1 = StreamConfig.from_dict(_req(d, "inputStream1", "config"),
                                     "inputStream1")
        in2_raw = d.get("inputStream2")
        in2 = (StreamConfig.from_dict(in2_raw, "inputStream2")
               if in2_raw else in1)
        return cls(
            cluster_mode=bool(_opt(d, "clusterMode", False)),
            kafka_bootstrap_servers=str(
                _opt(d, "kafkaBootStrapServers", "localhost:9092")),
            input1=in1,
            input2=in2,
            output=OutputStreamConfig.from_dict(_opt(d, "outputStream", {})),
            query=QueryConfig.from_dict(_req(d, "query", "config")),
            window=WindowConfig.from_dict(_req(d, "window", "config")),
        )

    @classmethod
    def from_yaml(cls, path: str) -> "Params":
        import yaml

        with open(path, "r", encoding="utf-8") as f:
            text = f.read()
        # strip the java type tag the reference's snakeyaml needs
        text = re.sub(r"^!!\S+\s*\n", "", text)
        data = yaml.safe_load(text)
        if not isinstance(data, dict):
            raise ConfigError(f"{path}: not a mapping")
        return cls.from_dict(data)

    # -------------------------- derived objects ----------------------- #

    def grids(self) -> Tuple[UniformGrid, UniformGrid]:
        """(uGrid, qGrid) like ``StreamingJob.java:309-315``."""
        return self.input1.make_grid(), self.input2.make_grid()

    def query_point_objects(self, grid: UniformGrid) -> List[Point]:
        return [Point.create(x, y, grid=grid)
                for x, y in self.query.query_points]

    def query_polygon_objects(self, grid: UniformGrid) -> List[Polygon]:
        return [Polygon.create([list(c)], grid=grid)
                for c in self.query.query_polygons]

    def query_linestring_objects(self, grid: UniformGrid) -> List[LineString]:
        return [LineString.create(list(c), grid=grid)
                for c in self.query.query_linestrings]

    def window_ms(self) -> Tuple[int, int]:
        return (int(self.window.interval_s * 1000),
                int(self.window.step_s * 1000))

    def validate_mesh(self) -> None:
        """Cross-field mesh validation — called AFTER CLI overrides land on
        top of the YAML (--devices/--hosts), so a valid combination split
        between the two sources isn't rejected at load time and an invalid
        CLI value fails with a config error, not a deep traceback."""
        h, p = self.query.hosts, self.query.parallelism
        if h < 0 or (h & (h - 1)):
            raise ConfigError("hosts: must be 0 (off) or a power of two")
        if p < 0 or (p & (p - 1)):
            raise ConfigError("parallelism: must be 0 (off) or a power of two")
        if h > 1 and (p == 0 or p % h):
            raise ConfigError(
                "hosts must divide parallelism (the 2-D mesh is "
                f"hosts x parallelism/hosts; got hosts={h}, parallelism={p})")

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def job_fingerprint(self, group: str = "") -> str:
        """Stable 8-hex digest of what makes two runs "the same job":
        consumer group + the full query and window blocks. Folded into
        KafkaWindowSink's idempotency keys so the dedup markers of one job
        configuration never suppress the windows of a different one sharing
        the output topic (two runs differing only in e.g. queryPoints or
        the window size answer different questions and must both produce).
        Transport and execution knobs (bootstrap servers, topic names,
        formats, mesh shape) are deliberately excluded: moving the same job
        to a different broker, re-encoding its input, or changing its
        device parallelism does not change what its windows mean — a
        sharded re-run must dedup against a single-device run's markers."""
        import hashlib
        import json

        query = dataclasses.asdict(self.query)
        query.pop("parallelism", None)
        query.pop("hosts", None)
        # pane mode (and its merge placement) is an execution strategy, not
        # a semantic change: a panes-on re-run must dedup against a
        # panes-off run's markers
        query.pop("panes", None)
        query.pop("pane_device_merge", None)
        payload = {
            "group": group,
            "query": query,
            "window": dataclasses.asdict(self.window),
        }
        return hashlib.sha1(
            json.dumps(payload, sort_keys=True, default=str).encode()
        ).hexdigest()[:8]
