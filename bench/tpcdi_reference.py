"""Plain reference of the TPC-DI DimTrade incremental update, in numpy only.

It imports nothing of the system under test: it works from the rows the
benchmark generated (Trade CDC rows, every DimAccount version, DimSecurity)
and from TPC-DI's transformation rules for DimTrade, computed here in
integer-exact int64 columns:

* the account join is point-in-time: the version of ``T_CA_ID`` with the
  latest ``EffectiveDate`` not after ``T_DTS`` (a version's ``EndDate`` is
  the next one's ``EffectiveDate``), found by binary search over the
  versions sorted by (account, EffectiveDate);
* the security join by symbol; ``Status`` and ``Type`` from the StatusType
  and TradeType rows (names coded in alphabetical order);
* ``SK_CreateDateID`` / ``SK_CreateTimeID`` from ``T_DTS`` (``YYYYMMDD``,
  ``HHMMSS``, through numpy's calendar) when the status is SBMT and the type
  TMB or TMS, or the status is PNDG; the close keys when the status is CMPT
  or CNCL; NULL (-1) otherwise; ``BatchID`` the incremental batch's
  number (2: batch 1 is the historical load);
* ``apply``: CDC rows applied to DimTrade in log order per TradeID: an
  insert adds the row, an update overwrites it, the date keys it leaves
  NULL keeping their stored values; each row counts the CDC rows applied
  to it (exactly once each).

``dtype`` is the precision the columns pass through: int64 for the
reference; the control passes every source column through float32, the
lanes the steelworks deployment uses, in the program's place.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

NULL = -1
# Trade CDC row, DimAccount version and DimSecurity row layouts
T_COLS = ("cdc_flag", "cdc_dsn", "t_id", "t_dts", "t_st_id", "t_tt_id",
          "t_is_cash", "t_s_symb", "t_qty", "t_bid_price", "t_ca_id",
          "t_exec_name", "t_trade_price", "t_chrg", "t_comm", "t_tax")
A_COLS = ("sk_account_id", "account_id", "sk_broker_id", "sk_customer_id",
          "status", "account_desc", "tax_status", "effective_date")
S_COLS = ("sk_security_id", "symbol", "issue", "status", "name",
          "exchange_id", "sk_company_id", "shares_outstanding")
OUT_COLS = ("trade_id", "sk_broker_id", "sk_create_date_id",
            "sk_create_time_id", "sk_close_date_id", "sk_close_time_id",
            "status", "type", "cash_flag", "sk_security_id", "sk_company_id",
            "quantity", "bid_price", "sk_customer_id", "sk_account_id",
            "executed_by", "trade_price", "fee", "commission", "tax",
            "batch_id")
BATCH_ID = 2                 # the first incremental batch
KEEP = ("sk_create_date_id", "sk_create_time_id", "sk_close_date_id",
        "sk_close_time_id")
# StatusType.txt and TradeType.txt, in file order (the ids' codes)
STATUS_TYPE = [("ACTV", "Active"), ("CMPT", "Completed"),
               ("CNCL", "Canceled"), ("PNDG", "Pending"),
               ("SBMT", "Submitted"), ("INAC", "Inactive")]
TRADE_TYPE = [("TMB", "Market Buy"), ("TMS", "Market Sell"),
              ("TSL", "Stop Loss"), ("TLS", "Limit Sell"),
              ("TLB", "Limit Buy")]


def _col(rows: np.ndarray, cols, name: str) -> np.ndarray:
    return rows[:, cols.index(name)]


def _lanes(a: np.ndarray, dtype) -> np.ndarray:
    return np.asarray(a).astype(dtype).astype(np.int64)


def date_time_keys(dts: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(YYYYMMDD, HHMMSS) of epoch seconds, through numpy's calendar."""
    t = np.asarray(dts, np.int64).astype("datetime64[s]")
    day = t.astype("datetime64[D]")
    month = day.astype("datetime64[M]")
    y = month.astype("datetime64[Y]").astype(np.int64) + 1970
    m = month.astype(np.int64) % 12 + 1
    d = (day - month).astype(np.int64) + 1
    secs = (t - day).astype(np.int64)
    return (y * 10000 + m * 100 + d,
            (secs // 3600) * 10000 + (secs // 60 % 60) * 100 + secs % 60)


def dimtrade_rows(trades: np.ndarray, accounts: np.ndarray,
                  securities: np.ndarray, dtype=np.int64
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """DimTrade rows [n, 21] (int64) of Trade CDC rows ``trades`` [n, 16]
    against every DimAccount version ``accounts`` [m, 8] and DimSecurity
    ``securities`` [k, 8]; ``found`` [n] marks rows both joins answered."""
    tr = _lanes(trades, dtype)
    ac = _lanes(accounts, dtype)
    se = _lanes(securities, dtype)
    n = len(tr)
    # point-in-time account join
    aid, eff = _col(ac, A_COLS, "account_id"), _col(ac, A_COLS,
                                                    "effective_date")
    akey = (aid << 32) + eff
    order = np.argsort(akey, kind="stable")
    ca, dts = _col(tr, T_COLS, "t_ca_id"), _col(tr, T_COLS, "t_dts")
    pos = np.searchsorted(akey[order], (ca << 32) + dts, side="right") - 1
    vi = order[np.clip(pos, 0, max(len(order) - 1, 0))]
    a_found = (pos >= 0) & (aid[vi] == ca) if len(ac) else np.zeros(n, bool)
    acct = ac[vi] if len(ac) else np.zeros((n, len(A_COLS)), np.int64)
    # security join by symbol
    sym = _col(se, S_COLS, "symbol")
    sorder = np.argsort(sym, kind="stable")
    q = _col(tr, T_COLS, "t_s_symb")
    sp = np.clip(np.searchsorted(sym[sorder], q), 0, max(len(sym) - 1, 0))
    si = sorder[sp]
    s_found = sym[si] == q
    sec = se[si]
    # domains
    st_names = sorted(nm for _, nm in STATUS_TYPE)
    tt_names = sorted(nm for _, nm in TRADE_TYPE)
    st_code = {c: i for i, (c, _) in enumerate(STATUS_TYPE)}
    tt_code = {c: i for i, (c, _) in enumerate(TRADE_TYPE)}
    st_name = np.array([st_names.index(nm) for _, nm in STATUS_TYPE])
    tt_name = np.array([tt_names.index(nm) for _, nm in TRADE_TYPE])
    st, tt = _col(tr, T_COLS, "t_st_id"), _col(tr, T_COLS, "t_tt_id")
    dom_ok = ((st >= 0) & (st < len(STATUS_TYPE)) & (tt >= 0)
              & (tt < len(TRADE_TYPE)))
    st_c, tt_c = np.clip(st, 0, len(st_name) - 1), np.clip(tt, 0,
                                                            len(tt_name) - 1)
    create = (((st == st_code["SBMT"])
               & np.isin(tt, [tt_code["TMB"], tt_code["TMS"]]))
              | (st == st_code["PNDG"]))
    close = np.isin(st, [st_code["CMPT"], st_code["CNCL"]])
    date_id, time_id = date_time_keys(np.maximum(dts, 0))
    out = np.empty((n, len(OUT_COLS)), np.int64)
    put = lambda name, v: out.__setitem__((slice(None),
                                           OUT_COLS.index(name)), v)
    put("trade_id", _col(tr, T_COLS, "t_id"))
    put("sk_broker_id", _col(acct, A_COLS, "sk_broker_id"))
    put("sk_create_date_id", np.where(create, date_id, NULL))
    put("sk_create_time_id", np.where(create, time_id, NULL))
    put("sk_close_date_id", np.where(close, date_id, NULL))
    put("sk_close_time_id", np.where(close, time_id, NULL))
    put("status", st_name[st_c])
    put("type", tt_name[tt_c])
    put("cash_flag", _col(tr, T_COLS, "t_is_cash"))
    put("sk_security_id", _col(sec, S_COLS, "sk_security_id"))
    put("sk_company_id", _col(sec, S_COLS, "sk_company_id"))
    put("quantity", _col(tr, T_COLS, "t_qty"))
    put("bid_price", _col(tr, T_COLS, "t_bid_price"))
    put("sk_customer_id", _col(acct, A_COLS, "sk_customer_id"))
    put("sk_account_id", _col(acct, A_COLS, "sk_account_id"))
    put("executed_by", _col(tr, T_COLS, "t_exec_name"))
    put("trade_price", _col(tr, T_COLS, "t_trade_price"))
    put("fee", _col(tr, T_COLS, "t_chrg"))
    put("commission", _col(tr, T_COLS, "t_comm"))
    put("tax", _col(tr, T_COLS, "t_tax"))
    put("batch_id", np.full(n, BATCH_ID))
    return out, a_found & s_found & dom_ok


def apply(rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """DimTrade after applying ``rows`` (in log order) by TradeID: (final
    rows sorted by TradeID, CDC rows applied to each). The last row of a
    TradeID gives every column except the ``KEEP`` date keys, which take
    its last non-NULL value (NULL if it never had one)."""
    rows = np.asarray(rows, np.int64)
    if not len(rows):
        return np.zeros((0, len(OUT_COLS)), np.int64), np.zeros(0, np.int64)
    tid = rows[:, OUT_COLS.index("trade_id")]
    order = np.lexsort((np.arange(len(rows)), tid))
    srt = rows[order]
    stid = tid[order]
    start = np.r_[0, np.nonzero(stid[1:] != stid[:-1])[0] + 1]
    end = np.r_[start[1:], len(srt)]
    final = srt[end - 1].copy()
    pos = np.arange(len(srt))
    for name in KEEP:
        c = OUT_COLS.index(name)
        has = np.where(srt[:, c] != NULL, pos, -1)
        last = np.maximum.reduceat(has, start)
        final[:, c] = np.where(last >= 0, srt[np.maximum(last, 0), c], NULL)
    return final, end - start
